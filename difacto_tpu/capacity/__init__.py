"""Table-capacity levers (ISSUE 19; ROADMAP item 3 "capacity = hosts x
fs x quantization"): the three composable multipliers of effective slot
rows per device behind SlotStore knobs.

- quantized slots (``slot_dtype`` int8/fp8): 8-bit codes with per-row
  scales riding the fused rows' spare scalar lanes — 4x rows per HBM
  byte, dequant/requant folded into the fused gather/scatter epilogue
  (ops/fused.quant_half, updaters/sgd_updater.row_epilogue);
- frequency-adaptive admission (``admit_min_count``; :mod:`.sketch`): a
  count-min sketch over the producers' hashed token stream gates slot
  allocation, so the zipf tail never costs a row; occupancy-pressure
  eviction (``evict_occupancy``, SlotStore.maybe_evict) reclaims stale
  rows;
- host-RAM cold tier (``cold_tier_rows``; :mod:`.tier`): the device
  table holds only the hot rows, the tail lives in host RAM, and rows
  promote/demote in batches on the dispatch thread.

All three default off; the defaults are byte-identical to the
pre-capacity trajectory.
"""

from .sketch import CountMinSketch, AdmissionFilter  # noqa: F401
from .tier import ColdTier  # noqa: F401
