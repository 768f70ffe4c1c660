"""Runtime jit-compile and device->host transfer tracing: the dynamic
half of the JAX flow analyzer (analysis/jaxflow.py is the static half),
mirroring how locktrace.py complements the lock-order model and
shared.py the race model.

Every steady-state-relevant jit program in the tree is created through
:func:`jit` instead of bare ``jax.jit``, and every *sanctioned*
device->host sync goes through :func:`fetch` instead of bare
``np.asarray``. Disabled (the default), both are pass-throughs —
``jit`` returns the raw ``jax.jit`` wrapper, ``fetch`` is one extra
function call around ``np.asarray`` — zero steady-state overhead.

With ``DIFACTO_JAXTRACE=1``:

- ``jit`` wraps the compiled function and records, per **creation
  site** (``relpath:lineno`` of the ``jit(...)`` call — byte-identical
  to the static analyzer's jit-site identity), the call count, the
  authoritative compile count (the wrapper's own jit cache size, so
  weak-typed scalar arguments never over-count), and the set of
  observed *compile keys*: static-argnum values by value, traced
  arrays by ``(shape, dtype)``, Python scalars by type (weak-typed —
  a new float value is NOT a new compile);
- ``fetch`` records each device->host transfer per call site. A
  transfer at a site the static model does not list as a declared sync
  point — or any implicit coercion that never went through ``fetch``
  and therefore shows up as compile-cache-stable wall time instead —
  is what the jax-host-sync rule exists to catch.

That shared identity is the point: the tier-1 gate (tests/
test_jaxflow.py) drives the serve path under ``DIFACTO_JAXTRACE=1``
and asserts (a) every observed jit site is a site the static model
knows and declares warm-bounded, (b) compiles STOP GROWING once the
bucket caps are warm — the "zero steady-state recompiles" claim,
previously only bench-measured — and (c) every observed transfer in
the dispatch loop is a declared fetch point. ``tools/jitmap.py``
merges both views for humans (``make jitmap``).

``DIFACTO_JAXTRACE_OUT=<path>`` dumps the observed sites as JSON at
process exit (same contract as DIFACTO_LOCKTRACE_OUT /
DIFACTO_RACETRACE_OUT).
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

# repo root: difacto_tpu/utils/jaxtrace.py -> two parents up from the
# package directory; sites are stored relative to it so they match the
# static analyzer's repo-relative paths (same convention as locktrace)
_ROOT = Path(__file__).resolve().parents[2]

_reg_mu = threading.Lock()          # guards _sites/_fetches (raw on purpose)
_sites: Dict[str, "_SiteStats"] = {}
_fetches: Dict[str, Dict[str, int]] = {}   # site -> {point, count}


class _SiteStats:
    __slots__ = ("calls", "compiles", "keys", "label")

    def __init__(self, label: str):
        self.calls = 0
        self.compiles = 0
        self.keys: set = set()
        self.label = label


def enabled() -> bool:
    # DIFACTO_HLOSCAN implies tracing: the HLO scan (utils/hloscan.py)
    # rides the same _TracedJit wrappers and jit-site identities, so
    # turning it on must install them even without DIFACTO_JAXTRACE
    return os.environ.get("DIFACTO_JAXTRACE", "") not in ("", "0") \
        or os.environ.get("DIFACTO_HLOSCAN", "") not in ("", "0")


def _site(depth: int = 2) -> str:
    fr = sys._getframe(depth)
    fn = fr.f_code.co_filename
    try:
        rel = Path(fn).resolve().relative_to(_ROOT).as_posix()
    except ValueError:
        rel = fn
    return f"{rel}:{fr.f_lineno}"


def _arg_key(args: tuple, kwargs: dict, statics: frozenset) -> tuple:
    """Approximate jit cache key: statics by VALUE, arrays by aval
    signature, Python scalars by TYPE (weak-typed: a new float value is
    not a new compile). Only used for the jitmap key display — the
    compile count itself comes from the jit cache size, which is
    authoritative."""
    out = []
    for i, a in enumerate(args):
        if i in statics:
            try:
                hash(a)
                out.append(("s", a))
            except TypeError:
                out.append(("s!", type(a).__name__))
        else:
            out.append(_leaf_key(a))
    for k in sorted(kwargs):
        out.append((k, _leaf_key(kwargs[k])))
    return tuple(out)


def _leaf_key(a):
    if a is None or isinstance(a, (bool,)):
        return ("c", a)
    if isinstance(a, (int, float, complex, str, bytes)):
        return ("py", type(a).__name__)        # weak-typed scalar
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(shape), str(dtype))
    if isinstance(a, (tuple, list)):
        return ("t", tuple(_leaf_key(x) for x in a))
    # pytrees (namedtuples land in the tuple branch above; dataclass
    # pytrees summarize by type — shapes inside don't vary in this tree)
    return ("o", type(a).__name__)


class _TracedJit:
    """Callable wrapper stamping per-site call/compile counts. Forwards
    attribute access to the underlying jit wrapper so callers can still
    reach lower()/clear_cache()/etc."""

    __slots__ = ("_fn", "site", "_statics")

    def __init__(self, fn, site: str, statics: frozenset):
        self._fn = fn
        self.site = site
        self._statics = statics

    def __call__(self, *args, **kwargs):
        # hloscan first: lowering only reads avals, so scanning BEFORE
        # the real dispatch keeps donated buffers untouched
        from . import hloscan
        if hloscan.enabled():
            hloscan.maybe_scan(self.site, self._fn, args, kwargs)
        out = self._fn(*args, **kwargs)
        key = _arg_key(args, kwargs, self._statics)
        try:
            compiled = int(self._fn._cache_size())
        except (AttributeError, TypeError):
            compiled = -1              # fall back to key-set cardinality
        with _reg_mu:
            st = _sites[self.site]
            st.calls += 1
            st.keys.add(key)
            st.compiles = compiled if compiled >= 0 else len(st.keys)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def _wrap(fun, jit_kwargs: dict, site: str):
    """Shared jit/pjit body: build the jax.jit wrapper and, when
    tracing, stamp it with the CALLER's creation-site identity."""
    import jax

    wrapped = jax.jit(fun, **jit_kwargs)
    if not enabled():
        return wrapped
    statics = jit_kwargs.get("static_argnums", ())
    if isinstance(statics, int):
        statics = (statics,)
    label = getattr(fun, "__name__", type(fun).__name__)
    with _reg_mu:
        _sites.setdefault(site, _SiteStats(label))
    return _TracedJit(wrapped, site, frozenset(statics))


def jit(fun, **jit_kwargs):
    """``jax.jit(fun, **jit_kwargs)``, traced when DIFACTO_JAXTRACE=1.

    The jit-site identity is the creation site of THIS call
    (``relpath:lineno``), byte-identical to the static jaxflow model's
    site ids — that is what lets the tier-1 gate compare observed
    compiles against the statically declared warm set."""
    return _wrap(fun, jit_kwargs, _site())


def pjit(fun, **jit_kwargs):
    """Sharded-jit creation with the SAME site identity contract as
    :func:`jit`: ``jax.jit`` has absorbed pjit, so this forwards
    ``in_shardings``/``out_shardings``/statics/donation to jax.jit —
    but the call is *named* pjit so the static analyzer's jit-site
    discovery (analysis/jaxflow.py ``_is_jit_name`` matches ``pjit`` /
    ``*.pjit``) and this tracer agree on one ``relpath:lineno``
    identity for the program. Mesh-sharded train/serve programs created
    through here stay inside the jax-recompile / donation / host-sync
    gates instead of dodging them behind a differently-named wrapper."""
    return _wrap(fun, jit_kwargs, _site())


def fetch(x, point: str = "") -> np.ndarray:
    """A DECLARED device->host sync: ``np.asarray(x)``, counted per
    call site when DIFACTO_JAXTRACE=1. The static analyzer treats
    ``jaxtrace.fetch(...)`` as the sanctioned coercion of device values
    on the hot path (analysis/jaxflow.py jax-host-sync) — implicit
    ``float()``/``np.asarray`` syncs there are findings; this is how a
    deliberate one is written down and audited at runtime."""
    if not enabled():
        return np.asarray(x)
    site = _site()
    with _reg_mu:
        per = _fetches.setdefault(site, {"point": point, "count": 0})
        per["count"] += 1
    return np.asarray(x)


# ----------------------------------------------------------------- data


def sites() -> Dict[str, dict]:
    """Snapshot: jit site -> {label, calls, compiles, keys}."""
    with _reg_mu:
        return {
            s: {"label": st.label, "calls": st.calls,
                "compiles": st.compiles,
                "keys": sorted(repr(k) for k in st.keys)}
            for s, st in _sites.items()
        }


def fetches() -> Dict[str, dict]:
    """Snapshot: fetch site -> {point, count}."""
    with _reg_mu:
        return {s: dict(rec) for s, rec in _fetches.items()}


def reset() -> None:
    with _reg_mu:
        _sites.clear()
        _fetches.clear()


def dump(path) -> str:
    """Write the observed jit/transfer sites as JSON; returns the path."""
    payload = {
        "version": 1,
        "sites": dict(sorted(sites().items())),
        "fetches": dict(sorted(fetches().items())),
    }
    p = Path(path)
    if p.parent and str(p.parent) not in (".", ""):
        p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(payload, indent=1) + "\n", encoding="utf-8")
    return str(p)


def load(path) -> dict:
    """Read a dump() file back: {'sites': {...}, 'fetches': {...}}."""
    data = json.loads(Path(path).read_text(encoding="utf-8"))
    if data.get("version") != 1:
        raise ValueError(f"jaxtrace dump {path}: unsupported version "
                         f"{data.get('version')!r}")
    return {"sites": dict(data.get("sites", {})),
            "fetches": dict(data.get("fetches", {}))}


def _atexit_dump() -> None:  # pragma: no cover - process teardown
    out = os.environ.get("DIFACTO_JAXTRACE_OUT", "")
    if out and enabled():
        try:
            dump(out)
        except OSError as e:
            print(f"jaxtrace: dump to {out} failed: {e}", file=sys.stderr)


atexit.register(_atexit_dump)
