"""Data feeding: reader decorators + prefetching DataLoader.

Reference counterparts:
  * python/paddle/reader decorators (shuffle/batch/xmap) — pure-python;
  * reader.py:45 PyReader + operators/reader/buffered_reader.cc — the
    lock-free queue + double-buffer (async H2D) pipeline;
  * framework/data_feed.cc Dataset — multithreaded file parsing.

TPU-first shape: a background thread converts numpy batches and
`jax.device_put`s them ahead of consumption (double/triple buffering), so
host->device transfer overlaps the device step exactly like
buffered_reader.cc overlapped cudaMemcpyAsync.

Stream-state protocol (ISSUE 5): every decorator here returns a callable
object that — when its source supports it — also implements

    state_dict()       position of the NEXT item the live iterator will
                       yield (call it between pulls)
    load_state_dict()  make the next __call__ resume exactly there

so a training run can checkpoint its data stream and resume O(1) instead
of replaying the dataset (tf.data/CheckFreq-style).  `is_checkpointable`
probes support; readers whose order is irreproducible (unordered xmap,
multi-threaded native queues) answer False and callers fall back to
replay.  The feed boundary is guarded by `FeedSpec`: a dtype/shape
mismatched (or, under FLAGS_feed_validation=full, non-finite) feed raises
a DataError naming the slot BEFORE lowering, instead of surfacing as an
opaque XLA error.
"""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .monitor import MONITOR as _MON


# --- stream-state protocol ---------------------------------------------------

def is_checkpointable(reader) -> bool:
    """True when `reader` speaks the stream-state protocol: state_dict /
    load_state_dict, and — if it defines a `checkpointable()` probe — that
    probe answers True (decorators over non-resumable sources keep the
    methods but answer False through the probe)."""
    probe = getattr(reader, "checkpointable", None)
    if callable(probe):
        try:
            if not probe():
                return False
        except Exception:
            return False
    return (callable(getattr(reader, "state_dict", None))
            and callable(getattr(reader, "load_state_dict", None)))


class _StatefulDecorator:
    """Base for the decorator classes below: callable exactly like the
    historical closures, plus the stream-state protocol delegated to the
    wrapped source reader(s).  One live iterator per instance at a time —
    the instance tracks that iterator's position."""

    _sources: tuple = ()

    def checkpointable(self) -> bool:
        return all(is_checkpointable(s) for s in self._sources)

    def _require_stateful(self, op: str):
        if not self.checkpointable():
            raise TypeError(
                f"{type(self).__name__}.{op}: the wrapped source reader is "
                f"not checkpointable (no state_dict/load_state_dict, or an "
                f"irreproducible order) — resume falls back to replay")


# --- reader decorators (reference: python/paddle/reader/decorator.py) ------

class _ShuffleReader(_StatefulDecorator):
    """Buffered shuffle with per-epoch reshuffling.

    The per-epoch RNG derives from `(seed, epoch)` so every epoch permutes
    differently while the whole schedule stays deterministic (the ISSUE 5
    satellite: the old implementation reshuffled in the identical order
    every epoch).  `seed=None` falls back to the program-level
    `random_seed` at iteration time, then to an unseeded RNG.  A private
    `random.Random` either way, so shuffling never perturbs the global
    `random` module's stream.

    Stream state: (epoch, source state at buffer start, RNG state at
    buffer start, offset into the current shuffled buffer).  Resume costs
    one buffer refill (`buf_size` source pulls), never a dataset replay.
    """

    def __init__(self, reader, buf_size: int, seed=None):
        self.reader = reader
        self.buf_size = buf_size
        self.seed = seed
        self._sources = (reader,)
        self._epoch = 0
        self._resume: Optional[dict] = None
        self._live: Optional[dict] = None

    def _resolve_seed(self):
        s = self.seed
        if s is None:
            try:
                from .core.program import default_main_program

                s = default_main_program().random_seed
            except Exception:
                s = None
        return s

    def state_dict(self) -> dict:
        self._require_stateful("state_dict")
        if self._live is not None:
            return dict(self._live)
        if self._resume is not None:
            return dict(self._resume)
        return {"epoch": self._epoch, "src": None, "rng": None, "offset": 0}

    def load_state_dict(self, state: dict):
        self._require_stateful("load_state_dict")
        self._resume = dict(state)
        self._live = None

    def __call__(self):
        import random

        resume, self._resume = self._resume, None
        epoch = int(resume["epoch"]) if resume is not None else self._epoch
        self._epoch = epoch + 1
        s = self._resolve_seed()
        rng = random.Random(s * 1_000_003 + epoch) if s is not None \
            else random.Random()
        src = self.reader
        stateful = is_checkpointable(src)
        skip = 0
        if resume is not None:
            if resume.get("src") is not None:
                src.load_state_dict(resume["src"])
            if resume.get("rng") is not None:
                rng.setstate(resume["rng"])
            skip = int(resume.get("offset", 0))
        it = src()
        while True:
            buf_state = {"epoch": epoch,
                         "src": src.state_dict() if stateful else None,
                         "rng": rng.getstate(), "offset": 0}
            buf = list(itertools.islice(it, self.buf_size))
            if not buf:
                if skip:
                    raise RuntimeError(
                        f"shuffle resume: source ended before the saved "
                        f"buffer position (offset {skip}) — the source must "
                        f"replay the same stream")
                self._live = buf_state  # end-of-epoch position
                return
            rng.shuffle(buf)
            if skip > len(buf):
                raise RuntimeError(
                    f"shuffle resume: saved offset {skip} exceeds the "
                    f"reconstructed buffer ({len(buf)} items) — the source "
                    f"stream changed since the state was saved")
            start, skip = skip, 0
            for i in range(start, len(buf)):
                buf_state["offset"] = i + 1
                self._live = buf_state
                yield buf[i]


def shuffle(reader: Callable, buf_size: int, seed: Optional[int] = None):
    """Buffered shuffle; see _ShuffleReader (per-epoch reshuffle, stream
    state when the source is checkpointable)."""
    return _ShuffleReader(reader, buf_size, seed)


class _BatchReader(_StatefulDecorator):
    """Stream state delegates live to the source: between batch yields the
    source sits exactly at the next batch's first sample."""

    def __init__(self, reader, batch_size: int, drop_last: bool):
        self.reader = reader
        self.batch_size = batch_size
        self.drop_last = drop_last
        self._sources = (reader,)

    def state_dict(self) -> dict:
        self._require_stateful("state_dict")
        return {"src": self.reader.state_dict()}

    def load_state_dict(self, state: dict):
        self._require_stateful("load_state_dict")
        self.reader.load_state_dict(state["src"])

    def __call__(self):
        b = []
        for item in self.reader():
            b.append(item)
            if len(b) == self.batch_size:
                yield b
                b = []
        if b and not self.drop_last:
            yield b


def batch(reader: Callable, batch_size: int, drop_last: bool = False):
    return _BatchReader(reader, batch_size, drop_last)


class _ChainReader(_StatefulDecorator):
    """Stream state = (active reader index, its state); readers before the
    active one are skipped outright on resume."""

    def __init__(self, *readers):
        self.readers = readers
        self._sources = readers
        self._resume: Optional[dict] = None
        self._live: Optional[dict] = None

    def state_dict(self) -> dict:
        self._require_stateful("state_dict")
        if self._live is not None:
            return dict(self._live)
        if self._resume is not None:
            return dict(self._resume)
        return {"index": 0, "src": None}

    def load_state_dict(self, state: dict):
        self._require_stateful("load_state_dict")
        self._resume = dict(state)
        self._live = None

    def __call__(self):
        resume, self._resume = self._resume, None
        start = 0
        if resume is not None:
            start = int(resume["index"])
            if start < len(self.readers) and resume.get("src") is not None:
                self.readers[start].load_state_dict(resume["src"])
        for i in range(start, len(self.readers)):
            r = self.readers[i]
            stateful = is_checkpointable(r)
            it = r()
            while True:
                try:
                    item = next(it)
                except StopIteration:
                    break
                self._live = {"index": i,
                              "src": r.state_dict() if stateful else None}
                yield item
        self._live = {"index": len(self.readers), "src": None}


def chain(*readers):
    return _ChainReader(*readers)


class _MapReader(_StatefulDecorator):
    """Stream state delegates live to the zipped sources (each advanced in
    lockstep between yields)."""

    def __init__(self, func, *readers):
        self.func = func
        self.readers = readers
        self._sources = readers

    def state_dict(self) -> dict:
        self._require_stateful("state_dict")
        return {"srcs": [r.state_dict() for r in self.readers]}

    def load_state_dict(self, state: dict):
        self._require_stateful("load_state_dict")
        for r, st in zip(self.readers, state["srcs"]):
            r.load_state_dict(st)

    def __call__(self):
        for items in zip(*[r() for r in self.readers]):
            yield self.func(*items)


def map_readers(func, *readers):
    return _MapReader(func, *readers)


class _XmapReader(_StatefulDecorator):
    """Parallel map over a reader via worker threads (decorator.py xmap).

    A mapper (or source-reader) exception must not strand the consumer: a
    worker that died without posting its END sentinel used to leave the
    consumer blocked on `out_q.get()` forever.  Workers post the exception
    itself (tagged with the sample index and a loader-phase breadcrumb for
    errors.classify), and the consumer re-raises it.

    Stream state: supported only with `order=True` over a checkpointable
    source (unordered output is irreproducible).  The feed thread snapshots
    the source state after each pull and threads it through the queues, so
    the state attached to the sample just yielded is exactly "the next
    source pull is sample i+1"; in-flight samples are re-pulled and
    re-mapped on resume."""

    def __init__(self, mapper, reader, process_num, buffer_size, order=False):
        self.mapper = mapper
        self.reader = reader
        self.process_num = process_num
        self.buffer_size = buffer_size
        self.order = order
        self._sources = (reader,)
        self._live: Optional[dict] = None

    def checkpointable(self) -> bool:
        return self.order and is_checkpointable(self.reader)

    def state_dict(self) -> dict:
        self._require_stateful("state_dict")
        if self._live is not None:
            return dict(self._live)
        return {"src": self.reader.state_dict()}

    def load_state_dict(self, state: dict):
        self._require_stateful("load_state_dict")
        self.reader.load_state_dict(state["src"])
        self._live = None

    def __call__(self):
        in_q: "queue.Queue" = queue.Queue(self.buffer_size)
        out_q: "queue.Queue" = queue.Queue(self.buffer_size)
        END = object()
        ERR = object()
        reader, mapper, process_num = self.reader, self.mapper, self.process_num
        stateful = self.checkpointable()

        def feed():
            try:
                it = reader()
                i = 0
                while True:
                    try:
                        sample = next(it)
                    except StopIteration:
                        break
                    st = reader.state_dict() if stateful else None
                    in_q.put((i, sample, st))
                    i += 1
            except BaseException as e:
                from .errors import attach_context

                out_q.put((ERR, attach_context(e, phase="loader")))
            finally:
                for _ in range(process_num):
                    in_q.put(END)

        def work():
            while True:
                s = in_q.get()
                if s is END:
                    out_q.put(END)
                    return
                i, sample, st = s
                try:
                    out_q.put((i, mapper(sample), st))
                except BaseException as e:
                    from .errors import attach_context

                    out_q.put((ERR, attach_context(e, batch_index=i,
                                                   phase="loader")))
                    out_q.put(END)  # this worker is done; keep END count right
                    return

        threading.Thread(target=feed, daemon=True).start()
        workers = [threading.Thread(target=work, daemon=True)
                   for _ in range(self.process_num)]
        for w in workers:
            w.start()
        done = 0
        if not self.order:
            while done < self.process_num:
                item = out_q.get()
                if item is END:
                    done += 1
                    continue
                if item[0] is ERR:
                    raise item[1]
                yield item[1]
            return
        pending = {}
        next_idx = 0

        def _emit(mapped, st):
            if st is not None:
                self._live = {"src": st}
            return mapped

        while done < self.process_num:
            item = out_q.get()
            if item is END:
                done += 1
                continue
            if item[0] is ERR:
                raise item[1]
            pending[item[0]] = (item[1], item[2])
            while next_idx in pending:
                mapped, st = pending.pop(next_idx)
                yield _emit(mapped, st)
                next_idx += 1
        while next_idx in pending:
            mapped, st = pending.pop(next_idx)
            yield _emit(mapped, st)
            next_idx += 1


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    return _XmapReader(mapper, reader, process_num, buffer_size, order)


class _CacheReader(_StatefulDecorator):
    """Materializes the full reader exactly once, up front, so a partially
    consumed first epoch can't truncate later epochs.  Once materialized
    the stream state is just an index — O(1) resume regardless of the
    source (a resume in a fresh process re-materializes first, so the
    source must still replay the same stream)."""

    def __init__(self, reader):
        self.reader = reader
        self._sources = ()
        self._data: Optional[list] = None
        self._resume_index = 0
        self._live: Optional[int] = None

    def checkpointable(self) -> bool:
        return True

    def state_dict(self) -> dict:
        if self._live is not None:
            return {"index": self._live}
        return {"index": self._resume_index}

    def load_state_dict(self, state: dict):
        self._resume_index = int(state.get("index", 0))
        self._live = None

    def __call__(self):
        if self._data is None:
            self._data = list(self.reader())
        start, self._resume_index = self._resume_index, 0
        for i in range(start, len(self._data)):
            self._live = i + 1
            yield self._data[i]


def cache(reader):
    return _CacheReader(reader)


class _FirstN(_StatefulDecorator):
    def __init__(self, reader, n: int):
        self.reader = reader
        self.n = n
        self._sources = (reader,)
        self._resume: Optional[dict] = None
        self._count = 0

    def state_dict(self) -> dict:
        self._require_stateful("state_dict")
        if self._resume is not None:
            # loaded but not yet iterating: report the loaded state, not
            # the stale live count (a checkpoint taken here must not lose
            # the yielded count and over-yield past n on resume)
            return dict(self._resume)
        return {"src": self.reader.state_dict(), "yielded": self._count}

    def load_state_dict(self, state: dict):
        self._require_stateful("load_state_dict")
        self.reader.load_state_dict(state["src"])
        self._resume = dict(state)

    def __call__(self):
        resume, self._resume = self._resume, None
        self._count = int(resume.get("yielded", 0)) if resume else 0
        it = self.reader()
        while self._count < self.n:
            try:
                item = next(it)
            except StopIteration:
                return
            self._count += 1
            yield item


def firstn(reader, n):
    return _FirstN(reader, n)


# --- elastic sample sharding (ISSUE 9) --------------------------------------

class _ShardReader(_StatefulDecorator):
    """Strided sample shard of a global stream: rank `r` of `world` yields
    exactly the samples whose GLOBAL index i satisfies i % world == r.
    Every rank iterates the same base stream and keeps its 1/world — the
    classic dp sharding that needs no index, and the ONE sharded layout
    whose cursors are exactly re-splittable when the world size changes.

    Stream state: `{"kind": "shard", rank, world, pos, base}` where `pos`
    is the next GLOBAL index this rank will examine (last yielded id + 1
    once iterating) and `base` is the wrapped reader's state at that
    position (None for a non-checkpointable base: resume then replays
    `pos` base items — loud, O(pos) — instead of seeking).

    Elastic N->M: `repartition_shard_states` merges all N ranks' cursors
    into the global consumed-prefix watermark G and deals M fresh cursors
    positioned at G — no sample dropped, none double-trained — reusing
    the highest rank's base state, which sits exactly at G.  See the
    docstring there for why that works."""

    def __init__(self, reader, rank: int, world: int):
        if not (0 <= int(rank) < int(world)):
            raise ValueError(f"shard: rank {rank} outside world {world}")
        self.reader = reader
        self.rank = int(rank)
        self.world = int(world)
        self._sources = (reader,)
        self._resume: Optional[dict] = None
        self._live: Optional[dict] = None

    def checkpointable(self) -> bool:
        # position is exact even over a stateless (but deterministic)
        # base — resume degrades to a loud replay fast-forward of `pos`
        # base items rather than an O(1) seek
        return True

    def _state(self, pos: int) -> dict:
        base = self.reader.state_dict() if is_checkpointable(self.reader) \
            else None
        return {"kind": "shard", "rank": self.rank, "world": self.world,
                "pos": int(pos), "base": base}

    def state_dict(self) -> dict:
        if self._live is not None:
            return dict(self._live)
        if self._resume is not None:
            return dict(self._resume)
        return self._state(0)

    def load_state_dict(self, state: dict):
        if state.get("kind") != "shard":
            raise ValueError(f"shard.load_state_dict: not a shard cursor "
                             f"({sorted(state)})")
        if (int(state["world"]) != self.world
                or int(state["rank"]) != self.rank):
            raise ValueError(
                f"shard.load_state_dict: cursor is for rank "
                f"{state['rank']}/{state['world']} but this reader is rank "
                f"{self.rank}/{self.world} — repartition the cursors "
                f"(reader.repartition_stream_states) instead of loading a "
                f"foreign rank's position")
        self._resume = dict(state)
        self._live = None

    def __call__(self):
        import logging

        resume, self._resume = self._resume, None
        pos = 0
        src = self.reader
        stateful = is_checkpointable(src)
        if resume is not None:
            pos = int(resume["pos"])
            if resume.get("base") is not None and stateful:
                src.load_state_dict(resume["base"])
                it = iter(src() if callable(src) else src)
            else:
                # loud replay fast-forward: the base is deterministic but
                # not seekable, so position by discarding `pos` items
                it = iter(src() if callable(src) else src)
                if pos:
                    _MON.counter("data.shard_replay").inc(pos)
                    logging.getLogger("paddle_tpu.reader").warning(
                        "shard resume: base reader is not checkpointable — "
                        "replaying %d item(s) to reach global position %d "
                        "(give the shard a stateful base for an O(1) seek)",
                        pos, pos)
                    for _ in range(pos):
                        try:
                            next(it)
                        except StopIteration:
                            raise RuntimeError(
                                f"shard resume: base stream ended at item "
                                f"< {pos} while fast-forwarding — the base "
                                f"must replay the same deterministic stream")
        else:
            it = iter(src() if callable(src) else src)
        self._live = {"kind": "shard", "rank": self.rank,
                      "world": self.world, "pos": pos,
                      "base": (resume or {}).get("base")
                      if resume is not None
                      else (src.state_dict() if stateful else None)}
        while True:
            try:
                item = next(it)
            except StopIteration:
                return
            i = pos
            pos += 1
            if i % self.world == self.rank:
                self._live = self._state(pos)
                yield item


def shard(reader, rank: int, world: int):
    """Strided 1/world sample shard for `rank`; see _ShardReader (exact
    elastic cursor repartitioning when the world size changes)."""
    return _ShardReader(reader, rank, world)


def repartition_shard_states(states: Sequence[dict], new_world: int
                             ) -> List[dict]:
    """Exactly re-split N shard cursors for M ranks.

    Why this is exact: in lock-step training every rank has yielded the
    same count j of samples, so the union of everything yielded is the
    contiguous global prefix [0, G) with G = max(pos_r), and the N
    cursor positions are exactly the multiset {G, G-1, ..., G-N+1} —
    one per residue class, since rank r's last yield was ≡ r (mod N).
    (Which RANK holds the maximum depends on where the stream last
    started: a previous repartition at a watermark not divisible by N
    rotates the assignment, so the check validates the multiset plus
    each rank's residue, never a fixed rank order.)  The M new strided
    shards all start examining at G: rank r' keeps the ids >= G with
    id % M == r', which partitions [G, ...) with nothing dropped and
    nothing repeated.  The old rank whose cursor sits at G saw its last
    yield at id G-1, so its base state is exactly at G and every new
    cursor can reuse it for an O(1) seek.

    Raises ValueError when the cursors do NOT describe such a prefix
    (mixed worlds, missing ranks, unequal yield counts) — the caller
    falls back to a loud replay fast-forward or refuses, never to a
    silent approximate split."""
    import copy

    if not states:
        raise ValueError("repartition_shard_states: no cursors")
    new_world = int(new_world)
    if new_world < 1:
        raise ValueError(f"repartition_shard_states: new_world={new_world}")
    by_rank: Dict[int, dict] = {}
    world = None
    for st in states:
        if not (isinstance(st, dict) and st.get("kind") == "shard"):
            raise ValueError(
                "repartition_shard_states: cursor is not a shard state")
        w, r = int(st["world"]), int(st["rank"])
        if world is None:
            world = w
        elif w != world:
            raise ValueError(
                f"repartition_shard_states: mixed worlds {world} vs {w}")
        if r in by_rank:
            raise ValueError(f"repartition_shard_states: duplicate rank {r}")
        by_rank[r] = st
    if sorted(by_rank) != list(range(world)):
        raise ValueError(
            f"repartition_shard_states: incomplete rank set "
            f"{sorted(by_rank)} for world {world}")
    G = max(int(st["pos"]) for st in by_rank.values())
    boundary = all(int(st["pos"]) == G for st in by_rank.values())
    if not boundary:
        got = sorted(int(st["pos"]) for st in by_rank.values())
        want = list(range(G - world + 1, G + 1))
        if got != want:
            raise ValueError(
                f"repartition_shard_states: rank cursors are not a "
                f"consistent prefix (positions {got}, expected the "
                f"multiset {want} for watermark {G}) — an exact N->M "
                f"split is impossible")
        for r, st in by_rank.items():
            p = int(st["pos"])
            if (p - 1) % world != r:
                raise ValueError(
                    f"repartition_shard_states: rank {r}'s cursor at pos "
                    f"{p} is not on its own residue class (last yield "
                    f"must be ≡ {r} mod {world}) — the cursors belong to "
                    f"a different shard layout")
    donor = next(st for st in by_rank.values() if int(st["pos"]) == G)
    return [{"kind": "shard", "rank": r, "world": new_world, "pos": G,
             "base": copy.deepcopy(donor.get("base"))}
            for r in range(new_world)]


def repartition_stream_states(states: Sequence[dict], new_world: int
                              ) -> List[dict]:
    """Re-split whole-pipeline cursors N->M by descending through
    single-source decorator states (`{"src": ...}` — batch readers and
    friends) to the shard layer.  Decorators whose state is rank-local
    (shuffle buffers, chain positions) cannot sit ABOVE the shard layer
    and repartition exactly; anything below it rides along via the donor
    base state."""
    if all(isinstance(s, dict) and s.get("kind") == "shard" for s in states):
        return repartition_shard_states(states, new_world)
    if all(isinstance(s, dict) and set(s) == {"src"} for s in states):
        inner = repartition_stream_states([s["src"] for s in states],
                                          new_world)
        return [{"src": st} for st in inner]
    if all(isinstance(s, dict) and set(s) == {"srcs"}
           and len(s["srcs"]) == 1 for s in states):
        # a single-source map_readers wrapper
        inner = repartition_stream_states([s["srcs"][0] for s in states],
                                          new_world)
        return [{"srcs": [st]} for st in inner]
    raise ValueError(
        "repartition_stream_states: no shard layer found in the cursors — "
        "only pipelines of single-source decorators over reader.shard() "
        "repartition exactly")


# --- FeedSpec: the feed-boundary contract -----------------------------------

def _kind_castable(src: np.dtype, dst: np.dtype) -> bool:
    """Whether feeding `src`-typed data into a `dst`-typed slot is a
    deliberate-looking conversion (the loader has always silently cast
    int64->int32 etc.) rather than a data bug: bool/int may widen into
    int/float, float stays float — but float into an int slot, or
    object/string data anywhere, is a mistake worth dying loudly on."""
    s, d = src.kind, dst.kind
    if s == d:
        return True
    if s == "b":
        return d in "iuf"
    if s in "iu":
        return d in "iuf"
    return False


class FeedSpec:
    """Schema of the feed boundary, built from the feed variables.

    `validate(name, arr)` raises a `DataError` carrying the slot name and
    a `phase="feed"` breadcrumb BEFORE the array reaches lowering — a
    mismatched feed otherwise surfaces steps later as an opaque XLA shape/
    dtype error with no pointer back to the offending slot.  Checks are
    governed by FLAGS_feed_validation: "off" (trust the caller), "shape"
    (default: dtype-kind + shape, wildcarding None/-1 spec dims), "full"
    (additionally scan floating feeds for NaN/Inf).  Names absent from the
    spec (LoD companions, extra side-channel arrays) pass through."""

    def __init__(self, feed_vars: Sequence):
        from .core.dtypes import as_np_dtype

        self.spec = {}
        for v in feed_vars:
            try:
                dt = np.dtype(as_np_dtype(v.dtype))
            except Exception:
                dt = None
            shape = getattr(v, "shape", None)
            self.spec[v.name] = (dt, tuple(shape) if shape is not None else None)

    @staticmethod
    def mode() -> str:
        from .flags import flag

        return flag("FLAGS_feed_validation")

    def _fail(self, name: str, why: str):
        from .errors import DataError

        raise DataError(f"feed validation: slot {name!r} {why} "
                        f"(caught at the feed boundary, before lowering)",
                        phase="feed")

    def validate(self, name: str, arr, mode: Optional[str] = None):
        mode = self.mode() if mode is None else mode
        if mode == "off" or name not in self.spec:
            return
        want_dt, want_shape = self.spec[name]
        a = np.asarray(arr)
        if want_dt is not None and a.dtype != want_dt \
                and not _kind_castable(a.dtype, want_dt):
            self._fail(name, f"has dtype {a.dtype} which cannot feed a "
                             f"{want_dt} slot")
        if want_shape is not None:
            ok = len(a.shape) == len(want_shape) and all(
                sd is None or sd < 0 or sd == ad
                for ad, sd in zip(a.shape, want_shape))
            if not ok:
                self._fail(name, f"has shape {tuple(a.shape)}, slot expects "
                                 f"{tuple(want_shape)} (None/-1 dims are "
                                 f"wildcards)")
        if mode == "full" and a.dtype.kind == "f" and a.size \
                and not np.isfinite(a).all():
            bad = int(a.size - np.isfinite(a).sum())
            self._fail(name, f"contains {bad} non-finite value(s) "
                             f"(NaN/Inf) under FLAGS_feed_validation=full")

    def validate_feed(self, feed: Dict, mode: Optional[str] = None):
        mode = self.mode() if mode is None else mode
        if mode == "off":
            return
        for name, arr in feed.items():
            self.validate(name, arr, mode)


# --- DataFeeder (reference: data_feeder.py) --------------------------------

class DataFeeder:
    """Converts a list of per-sample tuples into a feed dict of batched
    numpy arrays keyed by the given feed variables.  Every produced batch
    passes FeedSpec validation (dtype-kind/shape, optionally finiteness)."""

    def __init__(self, feed_list: Sequence, place=None, program=None):
        self.feed_vars = list(feed_list)
        self.feed_spec = FeedSpec(self.feed_vars)

    def decorate_reader(self, reader, multi_devices=False, num_places=None,
                        drop_last=True):
        """reference DataFeeder.decorate_reader: wrap a sample-batch reader
        into a feed-dict reader (checkpointable when `reader` is)."""
        return _MapReader(self.feed, reader)

    def feed_parallel(self, iterable, num_places=None):
        """reference DataFeeder.feed_parallel: under SPMD one global feed
        dict serves every device (GSPMD shards it), so this is feed()."""
        for item in iterable:
            yield self.feed(item)

    def feed(self, samples: Iterable) -> Dict[str, np.ndarray]:
        mode = FeedSpec.mode()
        cols = None
        for sample in samples:
            if cols is None:
                cols = [[] for _ in sample]
            for i, v in enumerate(sample):
                cols[i].append(np.asarray(v))
        out = {}
        for var, col in zip(self.feed_vars, cols):
            arr = np.stack(col)
            from .core.dtypes import as_np_dtype

            want = as_np_dtype(var.dtype)
            if arr.dtype != want:
                if not _kind_castable(arr.dtype, np.dtype(want)) \
                        and mode != "off":
                    self.feed_spec._fail(
                        var.name, f"has dtype {arr.dtype} which cannot feed "
                                  f"a {np.dtype(want)} slot")
                arr = arr.astype(want)
            shape = var.shape
            if shape is not None and len(shape) == arr.ndim + 1 and shape[-1] == 1:
                arr = arr[..., None]  # fluid's trailing label dim
            self.feed_spec.validate(var.name, arr, mode)
            out[var.name] = arr
        return out


# --- prefetching loader (PyReader / buffered_reader equivalent) ------------

class DataLoader:
    """Background-thread device prefetcher.

    `from_generator` mirrors fluid.io.DataLoader/PyReader: wrap a batch
    generator (yielding feed dicts or tuples), get an iterator of
    device-resident feed dicts, `capacity` batches deep.

    Checkpointable when the generator is: the producer thread snapshots
    the generator's stream state after each pull and threads it through
    the prefetch queue, so `state_dict()` on the consumer side reflects
    exactly the batches the CONSUMER has seen (the producer runs up to
    `capacity` batches ahead; those in-flight batches are re-staged on
    resume, never lost or double-fed).  Every staged feed passes FeedSpec
    validation before device placement."""

    def __init__(self, feed_list: Sequence, capacity: int = 2, device=None, sharding=None):
        self.feed_vars = list(feed_list)
        self.feed_spec = FeedSpec(self.feed_vars)
        self.capacity = capacity
        self.device = device
        self.sharding = sharding  # optional dict name->Sharding for SPMD
        self._gen: Optional[Callable] = None
        self._resume_state = None
        self._consumed_state = None

    @staticmethod
    def from_generator(feed_list: Sequence, capacity: int = 2, device=None, sharding=None,
                       iterable: bool = True):
        return DataLoader(feed_list, capacity, device, sharding)

    def set_batch_generator(self, gen: Callable):
        self._gen = gen
        return self

    def set_sample_list_generator(self, gen: Callable):
        feeder = DataFeeder(self.feed_vars)
        # a _MapReader keeps the stream-state protocol flowing through the
        # sample-list -> feed-dict conversion
        self._gen = _MapReader(feeder.feed, gen)
        return self

    # -- stream-state protocol ----------------------------------------------
    def checkpointable(self) -> bool:
        return self._gen is not None and is_checkpointable(self._gen)

    def state_dict(self) -> dict:
        if not self.checkpointable():
            raise TypeError("DataLoader.state_dict: the batch generator is "
                            "not checkpointable")
        if self._consumed_state is not None:
            return self._consumed_state
        return self._gen.state_dict()

    def load_state_dict(self, state: dict):
        if not self.checkpointable():
            raise TypeError("DataLoader.load_state_dict: the batch generator "
                            "is not checkpointable")
        self._resume_state = state
        self._consumed_state = state

    def _place(self, name, arr):
        """Stage one feed on device.  `sharding` is either a single
        Sharding applied to every feed or a dict name->Sharding; a feed
        missing from the dict falls back to `device` placement (labels
        replicate while images batch-shard, etc.)."""
        if self.sharding is not None:
            if isinstance(self.sharding, dict):
                spec = self.sharding.get(name)
                if spec is not None:
                    return jax.device_put(arr, spec)
            else:
                return jax.device_put(arr, self.sharding)
        if self.device is not None:
            return jax.device_put(arr, self.device)
        return jax.device_put(arr)

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        if self._gen is None:
            raise RuntimeError("DataLoader: call set_batch_generator first")
        q: "queue.Queue" = queue.Queue(self.capacity)
        END = object()
        name_dtypes = {}
        from .core.dtypes import as_np_dtype

        for v in self.feed_vars:
            name_dtypes[v.name] = as_np_dtype(v.dtype)

        stateful = self.checkpointable()
        if self._resume_state is not None:
            self._gen.load_state_dict(self._resume_state)
            self._resume_state = None

        stop = threading.Event()

        def _put(item) -> bool:
            """put that gives up when the consumer abandoned the iterator,
            so the producer can't block forever holding device buffers."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            produced = 0
            try:
                src = iter(self._gen())
                vmode = FeedSpec.mode()
                while True:
                    try:
                        item = next(src)
                    except StopIteration:
                        break
                    if stop.is_set():
                        return
                    # state AFTER this pull == "the next batch is item+1";
                    # attached to the item so the consumer-side state only
                    # advances when the consumer actually receives it
                    st = self._gen.state_dict() if stateful else None
                    if not isinstance(item, dict):
                        item = {v.name: a for v, a in zip(self.feed_vars, item)}
                    placed = {}
                    nbytes = 0
                    # one batch staged: validate, cast, hand to the device
                    # (`device_put` returns once the copy is queued; the
                    # runtime's own threads lay the bytes out and send
                    # them).  This thread's busy time over the loop's time
                    # is how close the loader's Python runs to its limit.
                    with _MON.span("reader.stage", batch=produced) as staging:
                        for n, a in item.items():
                            a = np.asarray(a)
                            # FeedSpec guard: a mismatched feed dies HERE,
                            # named, not steps later inside XLA
                            self.feed_spec.validate(n, a, vmode)
                            want = name_dtypes.get(n)
                            if want is not None and a.dtype != want:
                                a = a.astype(want)
                            if a.dtype == np.int64:
                                a = a.astype(np.int32)
                            elif a.dtype == np.float64:
                                a = a.astype(np.float32)
                            nbytes += a.nbytes
                            placed[n] = self._place(n, a)
                        staging.annotate(bytes=nbytes)
                    _MON.counter("reader.bytes_staged").inc(nbytes)
                    if not _put((placed, st)):
                        return
                    produced += 1
            except BaseException as e:  # propagate to the consumer thread
                # still raised AS ITSELF in the consumer (original type +
                # traceback, pinned by test_reader); the breadcrumb routes
                # it through errors.classify as a DataError so the
                # resilient loop knows it is a skippable data failure
                from .errors import attach_context

                _put(("__error__", attach_context(e, batch_index=produced,
                                                  phase="loader")))
            finally:
                _put(END)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                # checked per batch (not latched): enabling the monitor
                # mid-run starts producing wait spans from the live iterator
                if _MON.enabled:
                    _MON.gauge("reader.queue_depth").set(q.qsize())
                # consumer-side starvation: time blocked on the queue — a
                # deep total here means the input pipeline, not the device
                # step, is the bottleneck
                with _MON.span("reader.wait"):
                    item = q.get()
                if item is END:
                    return
                if isinstance(item, tuple) and len(item) == 2 and item[0] == "__error__":
                    # re-raise the producer's exception AS ITSELF: the
                    # instance still carries the generator frame's
                    # traceback, so user data bugs point at user code, not
                    # at a bare RuntimeError from this loop
                    raise item[1]
                placed, st = item
                if st is not None:
                    # set BEFORE the yield: once the consumer holds the
                    # batch, "next batch" is the attached state
                    self._consumed_state = st
                _MON.counter("reader.batches").inc()
                yield placed
        finally:
            # consumer exited (break/exception/GC): release the producer
            stop.set()


# PyReader is the reference's older name for the same machinery.
PyReader = DataLoader
