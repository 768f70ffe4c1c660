"""Data converter: any input format -> libsvm text or the binary rec cache.

Equivalent of the reference's ``task=convert`` (src/reader/converter.h:41-124)
with the same parameters: data_in/data_format -> data_out/data_out_format,
``chunk_size`` MB read granularity, optional ``part_size`` MB output splitting
(-1 = single output). The rec output is the npz-shard cache of rec.py — the
fast binary path that keeps TPU chips fed (SURVEY §7 hard part (e)).

Two rec upgrades over the reference's CRB converter:

- ``rec_localize`` (default on) stores members *pre-localized* (compacted
  uint32 index + sorted reversed-id ``uniq``, like CRB's compacted CSR,
  src/reader/crb_parser.h:16-47) so training epochs skip parse + unique;
- ``rec_batch_size`` aligns member row counts to the training batch size so
  cached batches never straddle members, and ``convert_threads`` text
  chunks are parsed/localized/compressed in parallel (the dmlc
  ThreadedParser role, src/reader/reader.h:42-44).
"""

from __future__ import annotations

import logging
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from ..config import KWArgs, Param
from ..utils import stream
from ..utils.locktrace import mutex
from .localizer import compact
from .reader import Reader
from .rec import write_rec_block
from .rowblock import RowBlock, RowBlockBuilder

log = logging.getLogger("difacto_tpu")


@dataclass
class _ConvertSpec:
    """Everything a convert worker process needs to convert ITS byte-range
    part of the input into member files — plain picklable values only."""
    data_in: str
    data_format: str
    out_dir: str
    member_suffix: str
    member_rows: int
    rec_localize: bool
    rec_compress: bool
    chunk_bytes: int
    n_parts: int


def _convert_member_arrays(blk: RowBlock, localize: bool):
    if localize:
        cblk, uniq, _ = compact(blk)
        return cblk, uniq
    return blk, None


def convert_part_iter(spec: _ConvertSpec, part: int):
    """Process-pool ``make_iter`` for the parallel text->rec convert: parse
    this part's byte range, slice into member-row blocks, localize and
    write each member directly from the worker (the OUTPUT is the file
    set, so nothing heavy rides the ring — just per-member stats).
    Deterministic per part (fixed byte ranges, fixed member naming), so
    the pool's retry/straggler re-issue contract holds: a re-run rewrites
    the same members through atomic renames."""
    from .parsers import get_parser
    from .reader import _byte_ranges, _iter_text_chunks, expand_uri

    if spec.data_format.lower() == "rec":
        blocks = iter(Reader(spec.data_in, "rec", part, spec.n_parts,
                             chunk_bytes=spec.chunk_bytes))

        def timed_blocks():
            it = blocks
            while True:
                t0 = time.perf_counter()
                blk = next(it, None)
                dt = time.perf_counter() - t0
                if blk is None:
                    return
                yield blk, dt
    else:
        parse = get_parser(spec.data_format)
        files, sizes = expand_uri(spec.data_in, with_sizes=True)
        ranges = _byte_ranges(files, sizes, part, spec.n_parts)

        def timed_blocks():
            for path, b, e in ranges:
                for ch in _iter_text_chunks(path, b, e, spec.chunk_bytes):
                    t0 = time.perf_counter()
                    blk = parse(ch)
                    dt = time.perf_counter() - t0
                    if blk.size:
                        yield blk, dt

    builder = RowBlockBuilder()
    pending_parse = 0.0
    n_member = 0
    bs = spec.member_rows

    def write_member(blk: RowBlock, parse_s: float):
        nonlocal n_member
        path = stream.join(
            spec.out_dir, f"part-{part:03d}-{n_member:05d}"
                          f"{spec.member_suffix}")
        t0 = time.perf_counter()
        cblk, uniq = _convert_member_arrays(blk, spec.rec_localize)
        write_rec_block(path, cblk, uniq=uniq,
                        compress=spec.rec_compress)
        n_member += 1
        return ("member", blk.size, stream.getsize(path), parse_s,
                time.perf_counter() - t0)

    for blk, dt in timed_blocks():
        if bs <= 0:  # -1: one member per read chunk
            yield write_member(blk, dt)
            continue
        pending_parse += dt
        start = 0
        while start < blk.size:
            take = min(bs - builder.num_rows, blk.size - start)
            builder.push(blk.slice(start, start + take))
            start += take
            if builder.num_rows >= bs:
                yield write_member(builder.build(), pending_parse)
                pending_parse = 0.0
                builder.clear()
    if builder.num_rows:
        yield write_member(builder.build(), pending_parse)


@dataclass
class ConverterParam(Param):
    data_in: str = ""
    data_format: str = ""
    data_out: str = ""
    data_out_format: str = ""
    part_size: int = -1      # MB per output part; -1 = one output
    chunk_size: float = 512  # MB per read chunk
    rec_localize: bool = True
    # rows per rec member. 0 (default) = auto: align to ``batch_size`` when
    # the convert config carries one (so converting with the training conf
    # yields batch-aligned members — the cached fast path's best layout),
    # else DEFAULT_MEMBER_ROWS. -1 = one member per read chunk (the old
    # default — members of millions of rows defeat the cached reader's
    # whole-member fast path, round-3 advisor medium).
    rec_batch_size: int = 0
    # training batch size, accepted here so ``task=convert`` with the
    # training config auto-aligns members (see rec_batch_size)
    batch_size: int = 0
    convert_threads: int = 0  # 0 = auto
    # worker PROCESSES for the text->rec convert; 0 = auto (process
    # workers on hosts with >= 4 cores, threads below — same heuristic as
    # the learner's producer_mode), 1 = force the in-process threaded
    # path. Parallel convert shards the input by byte range across the
    # existing ProcessProducerPool (each worker parses + localizes +
    # writes its members directly), so the one-time convert stops being
    # bounded by one interpreter (it measured 146k ex/s single-process —
    # slower than training itself, ISSUE 7).
    convert_procs: int = 0
    # member encoding: "rec2" (default — the zero-copy page-aligned
    # framing of rec2.py, mmap'd at read time) or "npz" (legacy v1)
    rec_encoding: str = "rec2"
    # zlib-compress rec members (npz encoding only). Default OFF: the rec
    # format exists to make STREAMING fast (the reference picked LZ4 for
    # the same reason, src/data/compressed_row_block.h:20-142) and zlib
    # decompress measured 68% of the streamed-epoch host-pack pass (1.32
    # of 1.93 s per 600k rows on the development host);
    # uncompressed members are ~2.6x larger but read at page-cache speed.
    # rec2 members are always raw.
    rec_compress: bool = False


# auto member size when no batch_size is given: large enough that member
# metadata amortizes, small enough that the cached reader's whole-member
# path stays in reach for common batch sizes
DEFAULT_MEMBER_ROWS = 8192


class Converter:
    def __init__(self) -> None:
        self.param: ConverterParam | None = None
        # filled by run(): rows, eps, parse_s, write_s, procs, members —
        # the per-stage convert accounting
        self.stats: dict = {}
        self._stage_lock = mutex()

    def member_rows(self) -> int:
        """Resolved rows-per-member (see ConverterParam.rec_batch_size):
        explicit > 0 wins; 0 = batch_size if given else
        DEFAULT_MEMBER_ROWS; -1 = chunk granularity (returns -1)."""
        p = self.param
        if p.rec_batch_size > 0:
            return p.rec_batch_size
        if p.rec_batch_size == 0:
            return p.batch_size or DEFAULT_MEMBER_ROWS
        return -1

    def init(self, kwargs: KWArgs) -> KWArgs:
        self.param, remain = ConverterParam.init_allow_unknown(kwargs)
        for req in ("data_in", "data_format", "data_out", "data_out_format"):
            if not getattr(self.param, req):
                raise ValueError(f"converter requires {req}")
        if self.param.data_out_format not in ("libsvm", "rec"):
            raise ValueError(
                f"unknown output format: {self.param.data_out_format}")
        if self.param.rec_encoding not in ("rec2", "npz"):
            raise ValueError(
                f"unknown rec_encoding: {self.param.rec_encoding!r} "
                "(rec2|npz)")
        return remain

    def member_suffix(self) -> str:
        from .rec2 import SUFFIX
        return SUFFIX if self.param.rec_encoding == "rec2" else ".npz"

    def _acc_stage(self, key: str, dt: float) -> None:
        # summed across parse/write worker threads (tiny critical section)
        with self._stage_lock:
            self.stats[key] = round(self.stats.get(key, 0.0) + dt, 4)

    def resolve_procs(self) -> int:
        """Worker-process count for the rec convert. Explicit wins; auto
        (0) engages processes only when cores can actually overlap (the
        learner's producer_mode heuristic) and the output is a single
        part (part_size splitting stays on the threaded path — its
        rollover bookkeeping is inherently serial)."""
        import os
        p = self.param
        if p.part_size > 0 or p.data_out_format != "rec":
            return 1
        if p.convert_procs > 0:
            return p.convert_procs
        ncpu = os.cpu_count() or 1
        return min(ncpu, 8) if ncpu >= 4 else 1

    def run(self) -> None:
        t0 = time.perf_counter()
        if self.param.data_out_format == "rec":
            procs = self.resolve_procs()
            if procs > 1:
                self._run_rec_parallel(procs)
            else:
                self._run_rec()
        else:
            self._run_libsvm()
        self.stats["convert_s"] = round(time.perf_counter() - t0, 3)
        self.stats["rows"] = self.num_rows
        if self.stats["convert_s"] > 0:
            self.stats["eps"] = round(
                self.num_rows / self.stats["convert_s"], 1)

    def _run_rec_parallel(self, procs: int) -> None:
        """Parallel text->rec convert across the existing
        ProcessProducerPool (ISSUE 7 satellite): the input is sharded by
        byte range over ``procs`` worker processes, each parsing +
        localizing + writing its own members (named ``part-PPP-NNNNN``),
        so the one-time convert scales with cores instead of being
        pinned to one interpreter. Members stay batch-aligned within
        each part; only each part's tail member runs short — the same
        shape the ``part_size`` splitter always produced."""
        import functools

        from .producer_pool import ProcessProducerPool
        p = self.param
        out_dir = self._open_rec_part(0, False)
        spec = _ConvertSpec(
            data_in=p.data_in, data_format=p.data_format,
            out_dir=out_dir, member_suffix=self.member_suffix(),
            member_rows=self.member_rows(),
            rec_localize=p.rec_localize, rec_compress=p.rec_compress,
            chunk_bytes=min(int(p.chunk_size * (1 << 20)), 32 << 20),
            n_parts=procs)
        log.info("reading data from %s in %s format (%d convert workers)",
                 p.data_in, p.data_format, procs)
        pool = ProcessProducerPool(
            procs, functools.partial(convert_part_iter, spec),
            n_workers=procs, depth=8, slot_bytes=1 << 20)
        nrows = members = out_bytes = 0
        parse_s = write_s = 0.0
        for _, item in pool:
            _, rows, nbytes, p_s, w_s = item
            nrows += rows
            members += 1
            out_bytes += nbytes
            parse_s += p_s
            write_s += w_s
        log.info("done. written %d examples", nrows)
        self.num_rows = nrows
        self.stats.update(procs=procs, members=members,
                          out_bytes=out_bytes, parse_s=round(parse_s, 3),
                          write_s=round(write_s, 3))

    # ------------------------------------------------------------- rec
    def _parsed_blocks(self, threads: int):
        """Parse text chunks on ``threads`` workers, yielding blocks in
        read order (the dmlc ThreadedParser role; native parsers and numpy
        release the GIL, so threads scale)."""
        from collections import deque

        p = self.param
        if p.data_format.lower() == "rec":
            yield from Reader(p.data_in, p.data_format, 0, 1,
                              chunk_bytes=int(p.chunk_size * (1 << 20)))
            return
        from .parsers import get_parser
        from .reader import _byte_ranges, _iter_text_chunks, expand_uri
        parse = get_parser(p.data_format)
        files, sizes = expand_uri(p.data_in, with_sizes=True)
        # read granularity small enough to keep every worker busy
        chunk_bytes = min(int(p.chunk_size * (1 << 20)), 32 << 20)

        def chunks():
            for path, b, e in _byte_ranges(files, sizes, 0, 1):
                yield from _iter_text_chunks(path, b, e, chunk_bytes)

        def timed_parse(ch):
            t0 = time.perf_counter()
            blk = parse(ch)
            self._acc_stage("parse_s", time.perf_counter() - t0)
            return blk

        with ThreadPoolExecutor(max_workers=threads) as ex:
            futs: deque = deque()
            for ch in chunks():
                futs.append(ex.submit(timed_parse, ch))
                while len(futs) >= 2 * threads:
                    blk = futs.popleft().result()
                    if blk.size:
                        yield blk
            while futs:
                blk = futs.popleft().result()
                if blk.size:
                    yield blk

    def _run_rec(self) -> None:
        """Parallel pipeline: threaded parse -> row-aligned member slicing
        -> threaded (localize + compress + write)."""
        import os
        p = self.param
        log.info("reading data from %s in %s format", p.data_in,
                 p.data_format)
        mr = self.member_rows()
        log.info("rec members: %s rows each",
                 mr if mr > 0 else "one read chunk of")
        if p.rec_batch_size == 0 and not p.batch_size and p.rec_localize:
            log.warning(
                "no batch_size given: members default to %d rows; pass "
                "the training batch_size (or rec_batch_size) so members "
                "come out batch-aligned — the cached reader re-compacts "
                "every batch of an unaligned member", DEFAULT_MEMBER_ROWS)
        threads = p.convert_threads or min(6, os.cpu_count() or 1)
        split = p.part_size > 0
        limit = p.part_size * (1 << 20) if split else None

        nrows = 0
        ipart = 0
        nblk = 0
        written = [0]  # compressed bytes in current part (approximate:
        # updated as write futures land; part rollover is checked between
        # member submissions)
        written_lock = mutex()  # += from concurrent workers
        out_dir = self._open_rec_part(ipart, split)

        def write_member(path: str, blk: RowBlock) -> int:
            t0 = time.perf_counter()
            if p.rec_localize:
                cblk, uniq, _ = compact(blk)
                write_rec_block(path, cblk, uniq=uniq,
                                compress=p.rec_compress)
            else:
                write_rec_block(path, blk, compress=p.rec_compress)
            sz = stream.getsize(path)
            self._acc_stage("write_s", time.perf_counter() - t0)
            with written_lock:
                written[0] += sz
            return sz

        def member_blocks(blocks):
            """Re-slice parsed blocks into member-row-count members,
            carrying remainders across blocks (batches never straddle
            members, data/cached.py)."""
            bs = self.member_rows()
            if bs <= 0:  # -1: one member per read chunk
                yield from blocks
                return
            builder = RowBlockBuilder()
            for blk in blocks:
                start = 0
                while start < blk.size:
                    take = min(bs - builder.num_rows, blk.size - start)
                    builder.push(blk.slice(start, start + take))
                    start += take
                    if builder.num_rows >= bs:
                        yield builder.build()
                        builder.clear()
            if builder.num_rows:
                yield builder.build()

        futures = []
        with ThreadPoolExecutor(max_workers=threads) as ex:
            for blk in member_blocks(self._parsed_blocks(threads)):
                if split and written[0] >= limit:
                    for f in futures:  # part boundary: settle sizes
                        f.result()
                    futures.clear()
                    ipart += 1
                    nblk = 0
                    written[0] = 0
                    out_dir = self._open_rec_part(ipart, split)
                path = stream.join(out_dir,
                                   f"part-{nblk:05d}{self.member_suffix()}")
                futures.append(ex.submit(write_member, path, blk))
                nblk += 1
                nrows += blk.size
                if len(futures) >= 2 * threads:
                    futures.pop(0).result()
            for f in futures:
                f.result()
        log.info("done. written %d examples", nrows)
        self.num_rows = nrows

    def _open_rec_part(self, ipart: int, split: bool) -> str:
        path = self.param.data_out + (f"-part_{ipart}" if split else "")
        stream.makedirs(path)
        log.info("writing data to %s in rec format", path)
        return path

    # ------------------------------------------------------------- libsvm
    def _run_libsvm(self) -> None:
        p = self.param
        reader = Reader(p.data_in, p.data_format, 0, 1,
                        chunk_bytes=int(p.chunk_size * (1 << 20)))
        log.info("reading data from %s in %s format", p.data_in, p.data_format)
        split = p.part_size > 0
        limit = p.part_size * (1 << 20) if split else None

        ipart = 0
        nwrite = 0
        nrows = 0
        out = None

        def open_part():
            nonlocal out, nwrite, ipart
            path = p.data_out + (f"-part_{ipart}" if split else "")
            ipart += 1
            nwrite = 0
            out = stream.open_stream(path, "w")
            log.info("writing data to %s in libsvm format", path)
            return out

        out = open_part()
        for blk in reader:
            if split and nwrite >= limit:
                out.close()
                out = open_part()
            nwrite += self._write_text_block(out, blk)
            nrows += blk.size
        if out is not None:
            out.close()
        log.info("done. written %d examples", nrows)
        self.num_rows = nrows

    def _write_text_block(self, out, blk: RowBlock) -> int:
        # vectorised token formatting; only the per-row join is Python
        idx = np.char.mod("%d", blk.index.astype(np.uint64))
        if blk.value is not None:
            feats = np.char.add(np.char.add(idx, ":"),
                                np.char.mod("%g", blk.value))
        else:
            feats = np.char.add(idx, ":1")
        labels = np.char.mod("%g", blk.label)
        off = blk.offset
        lines = [labels[i] + " " + " ".join(feats[off[i]:off[i + 1]])
                 for i in range(blk.size)]
        data = "\n".join(lines) + "\n"
        out.write(data)
        return len(data)
