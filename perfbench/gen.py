"""Traffic: seed-generated criteo-shaped rows of a planted FM.

The shape of ``tools/download.synth_criteo``'s sampling (integer fields
drawn uniformly, categorical fields drawn from a zipf law, labels from a
planted linear + low-rank model), emitted as arrays and not as text. One
general generator: a traffic file (``traffic/<name>.json``) gives the
parameters (the number of tokens of each field, the zipf exponent, the
share of clicks) and names where they come from; ``--seed`` gives the
draw. Imports nothing of the program.

A feature is a (field, token) pair. Its id is what the criteo parser would
make, ``hash << 12 | field``, held here byte-reversed (``rev``) as the
program's rec members and its hashed store hold it: the field's three
nibbles on top, 52 random bits below. A field's tokens take ascending ids
in the order of their rank, so the ids come out sorted and no two are
alike; the row of the hashed table is the id modulo the capacity, which
scatters them all the same.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    int_fields: int = 13
    int_tokens: int = 1000
    cat_fields: int = 26
    # tokens of each categorical field: one number for all, or a list
    cat_tokens: object = 100_000
    zipf_a: float = 1.25
    w_scale: float = 0.20
    v_scale: float = 0.16
    v_fields: int = 8
    v_rank: int = 8
    int_scale: float = 0.05
    # the share of rows labelled 1: the planted score's offset is set for
    # each seed so that the mean click probability is this, and every
    # seed's first steps run in the same regime (left to the draw, the
    # rate ran from 2% to 68% over six seeds)
    ctr: float = 0.25

    @property
    def width(self) -> int:
        return self.int_fields + self.cat_fields

    @property
    def cat_sizes(self) -> list:
        if isinstance(self.cat_tokens, int):
            return [self.cat_tokens] * self.cat_fields
        if len(self.cat_tokens) != self.cat_fields:
            raise ValueError("cat_tokens lists one size a categorical "
                             "field")
        return [int(n) for n in self.cat_tokens]

    @property
    def sizes(self) -> list:
        return [self.int_tokens] * self.int_fields + self.cat_sizes

    @property
    def n_features(self) -> int:
        return sum(self.sizes)


@dataclasses.dataclass
class Tables:
    """What a seed fixes for every row: the features' reversed ids and the
    planted model. ``g`` numbers the features field by field, ints first."""
    spec: Spec
    base: np.ndarray        # int64[width]   first g of each field
    rev_sorted: np.ndarray  # uint64[G]      reversed ids, ascending
    rank: np.ndarray        # int32[G]       g -> position in rev_sorted
    cat_size: np.ndarray    # int64[cat_fields]  tokens of each field
    cat_base: np.ndarray    # int64[cat_fields]  its first row of w_tab
    w_tab: np.ndarray       # f32[all categorical tokens]
    v_tab: np.ndarray       # f32[tokens of the first v_fields, v_rank]
    w_int: np.ndarray       # f64[int_fields]
    bias: float = 0.0       # offset of the planted score, from ``ctr``

    def rev_of(self, g: np.ndarray) -> np.ndarray:
        return self.rev_sorted[self.rank[g]]


def make_tables(seed: int, spec: Spec) -> Tables:
    sizes = np.asarray(spec.sizes, np.int64)
    G = int(sizes.sum())
    base = np.concatenate(([0], np.cumsum(sizes)[:-1])).astype(np.int64)
    f = np.arange(spec.width, dtype=np.uint64)
    # the field id's nibbles, lowest first, land on top of the reversed id
    top = (((f & np.uint64(0xF)) << np.uint64(8)) | (f & np.uint64(0xF0))
           | ((f >> np.uint64(8)) & np.uint64(0xF)))
    rng = np.random.default_rng([int(seed), 0])
    order = np.argsort(top, kind="stable")          # fields by their top
    pos0 = np.empty(spec.width, np.int64)
    pos0[order] = np.concatenate(([0], np.cumsum(sizes[order])[:-1]))
    rank = np.empty(G, np.int32)
    rev_sorted = np.empty(G, np.uint64)
    for fld in range(spec.width):
        n, b0, p0 = int(sizes[fld]), int(base[fld]), int(pos0[fld])
        # the low 52 bits: running sums of random gaps that cannot pass
        # 2**52 together, so a field's ids ascend and none repeats
        low = np.cumsum(rng.integers(1, (1 << 52) // n, n, dtype=np.uint64))
        rev_sorted[p0:p0 + n] = (top[fld] << np.uint64(52)) | low
        rank[b0:b0 + n] = np.arange(p0, p0 + n, dtype=np.int32)
    cat_size = sizes[spec.int_fields:]
    cat_base = np.concatenate(([0], np.cumsum(cat_size)[:-1]))
    n_v = int(cat_size[:spec.v_fields].sum())
    t = Tables(
        spec=spec, base=base, rev_sorted=rev_sorted, rank=rank,
        cat_size=cat_size, cat_base=cat_base,
        w_tab=rng.standard_normal(int(cat_size.sum()), dtype=np.float32)
        * np.float32(spec.w_scale),
        v_tab=rng.standard_normal((n_v, spec.v_rank), dtype=np.float32)
        * np.float32(spec.v_scale),
        w_int=rng.standard_normal(spec.int_fields) * spec.int_scale)
    # the offset at which a sample of rows clicks at the rate ``ctr``
    _, _, score = _draw(np.random.default_rng([int(seed), 2]), 16384, t)
    lo, hi = -30.0, 30.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(score + mid)))) < spec.ctr:
            lo = mid
        else:
            hi = mid
    t.bias = 0.5 * (lo + hi)
    return t


def _draw(rng, rows: int, t: Tables):
    """(ints, toks, planted score without its offset) of ``rows`` rows."""
    s = t.spec
    ints = rng.integers(0, s.int_tokens, (rows, s.int_fields))
    toks = (rng.zipf(s.zipf_a, (rows, s.cat_fields)) - 1) % t.cat_size
    at = toks + t.cat_base
    score = (t.w_tab[at].sum(1, dtype=np.float64)
             + (np.log1p(ints) * t.w_int).sum(1))
    emb = t.v_tab[at[:, :s.v_fields]].astype(np.float64)
    xv = emb.sum(1)
    score += 0.5 * ((xv ** 2).sum(1) - (emb ** 2).sum((1, 2)))
    return ints, toks, score


def make_member(seed: int, m: int, rows: int, t: Tables):
    """Member ``m`` of ``rows`` rows: (label f32[rows] of 0/1,
    g int32[rows, width])."""
    rng = np.random.default_rng([int(seed), 1, int(m)])
    ints, toks, score = _draw(rng, rows, t)
    prob = 1.0 / (1.0 + np.exp(-(score + t.bias)))
    label = (rng.random(rows) < prob).astype(np.float32)
    g = np.concatenate([ints, toks], axis=1) + t.base[None, :]
    return label, g.astype(np.int32)


def localize(g: np.ndarray, t: Tables):
    """A member as the program's pre-localized rec members hold it:
    (uniq uint64 ascending reversed ids, index uint32[rows*width] of
    positions into uniq). The ids ascend with their rank, so the small
    integers are sorted and not the ids."""
    present, index = np.unique(t.rank[g.reshape(-1)], return_inverse=True)
    return t.rev_sorted[present], index.astype(np.uint32)


def members(seed: int, n_members: int, rows: int, t: Tables,
            sink, threads: int = 0):
    """Make members 0..n_members-1 on a few threads and hand each to
    ``sink(m, label, g, uniq, index)`` as it is done (any order)."""
    threads = threads or max(1, min(8, (os.cpu_count() or 2) - 1))

    def one(m):
        label, g = make_member(seed, m, rows, t)
        uniq, index = localize(g, t)
        sink(m, label, g, uniq, index)

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, range(n_members)))


def slots_of(rev: np.ndarray, hash_capacity: int) -> np.ndarray:
    """The hashed table's row of a feature, as the configuration states
    it (``hash_capacity``): reversed id modulo capacity - 1, plus 1; row 0
    takes no feature. Features that share a row share its parameters."""
    cap = np.uint64(hash_capacity - 1)
    return (rev % cap + np.uint64(1)).astype(np.int64)
