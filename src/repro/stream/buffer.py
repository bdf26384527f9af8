"""Fixed-capacity, sentinel-padded edge buffer for dynamic graphs.

The static pipeline compiles one executable per padded edge-array shape
(graphs/graph.py). A dynamic graph would re-pad — and therefore recompile —
on every update batch. ``EdgeBuffer`` removes that: undirected edges live in
``capacity`` slots (capacity is always a power of two), empty slots hold the
sentinel vertex ``n_nodes``, and the device view is the same symmetric COO
layout the peeling kernels already consume (``src = [u | v]``,
``dst = [v | u]``, shape ``[2 * capacity]``). Capacity only ever *doubles*,
so a graph that grows through k batches passes through at most log2 distinct
shapes — every other batch is a jit cache hit (the "no recompiles on the hot
path" contract, asserted in tests/test_stream.py).

Deletions punch holes (slot -> sentinel) instead of compacting, so a
batch moves O(batch) slots and device lanes; freed slots are recycled
hole-first for later insertions. The ``epoch_compact`` hook rebuilds a dense prefix when the
delta engine runs its staleness refresh, and with ``shrink=True`` also
*halves capacity down* to the smallest pow-2 that keeps 2x headroom — the
ISSUE 3 bugfix for sliding-window/delete-heavy tenants that otherwise kept
peak-size slot arrays forever. Hysteresis: a shrink fires only when live
edges occupy <= ``SHRINK_FRACTION`` of capacity, and lands at <= 50%
occupancy, so an oscillating graph cannot thrash grow/shrink.

Delete-heavy streams also fragment the slot space with tombstones faster
than any epoch cadence cleans them up; when the un-recycled-hole fraction
exceeds ``compact_threshold`` the buffer compacts itself mid-stream
(bumping ``generation`` so resident device state and compiled executables
re-bucket correctly).

Host-side membership is the streaming analog of the paper's "super map": a
sorted int64 array of the live canonical keys ``u * n_nodes + v`` (u < v),
with each key's slot in an aligned int32 array. A batch is a handful of
whole-array passes — a sort, ``searchsorted`` lookups, one boolean
compress for the deletes and one merge for the inserts — so its cost is
O(batch * log E + E) memmove, with no Python step per pair.
Sorted keys are also the compacted layout: ``epoch_compact`` writes them
into the dense prefix as they are.
"""
from __future__ import annotations

import numpy as np

from repro.graphs.graph import Graph
from repro.obs.trace import span
from repro.utils.num import next_pow2

MIN_CAPACITY = 256  # matches Graph.from_edges pad_multiple: shared jit shapes
SHRINK_FRACTION = 0.25  # epoch shrink only below 25% occupancy (hysteresis)
TOMBSTONE_COMPACT_FRACTION = 0.5  # default mid-stream compaction trigger


class EdgeBuffer:
    """Mutable undirected edge set with a static-shape device view."""

    def __init__(self, n_nodes: int, capacity: int = MIN_CAPACITY,
                 compact_threshold: float | None = TOMBSTONE_COMPACT_FRACTION,
                 min_capacity: int = MIN_CAPACITY):
        if n_nodes <= 0:
            raise ValueError("EdgeBuffer needs n_nodes >= 1")
        # min_capacity floors every shrink (and the initial size): sharded
        # engines raise it so the slot space never drops below one lane
        # block per mesh device
        self.min_capacity = max(next_pow2(min_capacity), MIN_CAPACITY)
        capacity = max(next_pow2(capacity), self.min_capacity)
        self.n_nodes = int(n_nodes)
        self.capacity = capacity
        self.compact_threshold = compact_threshold
        self._u = np.full(capacity, n_nodes, dtype=np.int32)
        self._v = np.full(capacity, n_nodes, dtype=np.int32)
        # membership: live keys u * n_nodes + v (u < v), ascending, and
        # each key's slot
        self._keys = np.empty(0, dtype=np.int64)
        self._kslot = np.empty(0, dtype=np.int32)
        # never-used slots are always the range [_fresh, capacity), taken
        # lowest first; freed slots (holes) are a stack, newest on top, so
        # fragmentation is observable and holes recycle first (dense
        # prefixes survive churn longer)
        self._fresh = 0
        self._holes = np.empty(0, dtype=np.int32)
        self.generation = 0  # bumped on every grow/compact (shape/layout epoch)
        self._version = 0    # bumped on every mutation (sorted-view cache key)
        self._sorted_cache: tuple | None = None

    # -- properties ---------------------------------------------------------
    @property
    def n_edges(self) -> int:
        return self._keys.size

    @property
    def sentinel(self) -> int:
        return self.n_nodes

    @property
    def tombstone_fraction(self) -> float:
        """Fraction of the slot space holding un-recycled delete holes."""
        return self._holes.size / self.capacity

    def __contains__(self, edge: tuple[int, int]) -> bool:
        u, v = sorted((int(edge[0]), int(edge[1])))
        if not 0 <= u < v < self.n_nodes:
            return False
        key = u * self.n_nodes + v
        i = int(np.searchsorted(self._keys, key))
        return i < self._keys.size and int(self._keys[i]) == key

    def live_pairs(self) -> np.ndarray:
        """The live edges as canonical pairs (u < v), ``[n_edges, 2]``
        int64, sorted by (u, v)."""
        return np.stack(np.divmod(self._keys, self.n_nodes), axis=1)

    # -- mutation -----------------------------------------------------------
    def _keys_of(self, edges: np.ndarray) -> np.ndarray:
        """Canonical keys ``u * n_nodes + v`` (u < v) of a batch, in batch
        order; self-loops dropped (simple-graph convention)."""
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if edges.size and (edges.min() < 0 or edges.max() >= self.n_nodes):
            raise ValueError(
                f"edge endpoint out of range [0, {self.n_nodes}): "
                f"min={edges.min()} max={edges.max()}"
            )
        u = np.minimum(edges[:, 0], edges[:, 1])
        v = np.maximum(edges[:, 0], edges[:, 1])
        return (u * self.n_nodes + v)[u != v]

    def _find(self, key: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Index positions of the ascending ``key`` and which are live."""
        pos = np.searchsorted(self._keys, key)
        if not self._keys.size:
            return pos, np.zeros(key.size, dtype=bool)
        return pos, self._keys[np.minimum(pos, self._keys.size - 1)] == key

    def apply(
        self, insert: np.ndarray | None = None, delete: np.ndarray | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Apply a batch. Returns the *effective*
        ``(inserted [k,2], ins_slots [k], deleted [m,2], del_slots [m])``:
        inserts already present and deletes of absent edges are dropped.
        Deletes are applied first (stream semantics: a batch is a set of
        retractions followed by assertions), so an insert may reuse a slot
        freed by a delete in the same batch. Deletes come back in batch
        order (a repeated pair at its first position), inserts in (u, v)
        order. Slot indices let the delta engine patch its device-resident
        arrays in O(batch).

        If the batch leaves the tombstone fraction above
        ``compact_threshold`` the buffer compacts itself before returning
        (``generation`` bumps, so callers holding device state must resync —
        the returned slot indices refer to the pre-compaction layout)."""
        n = self.n_nodes
        deleted = del_slots = np.empty(0, dtype=np.int64)
        if delete is not None:
            key = self._keys_of(delete)
            order = np.argsort(key)
            key = key[order]
            # one entry per distinct key, tagged with its first batch index
            run = np.flatnonzero(np.diff(key, prepend=-1))
            first = np.minimum.reduceat(order, run) if run.size else run
            pos, live = self._find(key[run])
            # back to batch order (the first indices are distinct)
            pos = pos[live][np.argsort(first[live])]
            deleted = self._keys[pos]
            del_slots = self._kslot[pos]
            keep = np.ones(self._keys.size, dtype=bool)
            keep[pos] = False
            self._keys = self._keys[keep]
            self._kslot = self._kslot[keep]
            self._u[del_slots] = self.sentinel
            self._v[del_slots] = self.sentinel
            self._holes = np.concatenate([self._holes, del_slots])
        inserted = ins_slots = np.empty(0, dtype=np.int64)
        if insert is not None:
            key = np.unique(self._keys_of(insert))
            pos, live = self._find(key)
            inserted, pos = key[~live], pos[~live]
            # grow once, up front, if the effective batch cannot fit
            if self._keys.size + inserted.size > self.capacity:
                self._grow(next_pow2(self._keys.size + inserted.size))
            ins_slots = self._take_slots(inserted.size)
            self._merge(pos, inserted, ins_slots)
            self._u[ins_slots], self._v[ins_slots] = np.divmod(inserted, n)
        self._version += 1
        if (self.compact_threshold is not None
                and self._holes.size > self.compact_threshold * self.capacity):
            self.epoch_compact()
        return (
            np.stack(np.divmod(inserted, n), 1).astype(np.int32),
            ins_slots.astype(np.int32),
            np.stack(np.divmod(deleted, n), 1).astype(np.int32),
            del_slots.astype(np.int32),
        )

    def _merge(self, pos: np.ndarray, key: np.ndarray,
               slots: np.ndarray) -> None:
        """Insert the ascending absent ``key`` (with their ``slots``) into
        the index before positions ``pos``: one pass, ``np.insert``'s
        result without its per-call overhead."""
        at = pos + np.arange(pos.size)
        old = np.ones(self._keys.size + pos.size, dtype=bool)
        old[at] = False
        keys = np.empty(old.size, dtype=np.int64)
        kslot = np.empty(old.size, dtype=np.int32)
        keys[at], keys[old] = key, self._keys
        kslot[at], kslot[old] = slots, self._kslot
        self._keys, self._kslot = keys, kslot

    def _take_slots(self, k: int) -> np.ndarray:
        """``k`` free slots: the newest holes first, then the lowest
        never-used slots."""
        h = min(k, self._holes.size)
        rest = self._holes.size - h
        taken = np.concatenate([
            self._holes[rest:][::-1],
            np.arange(self._fresh, self._fresh + k - h, dtype=np.int32)])
        self._holes = self._holes[:rest]
        self._fresh += k - h
        return taken

    def _grow(self, new_capacity: int) -> None:
        new_capacity = max(next_pow2(new_capacity), 2 * self.capacity)
        u = np.full(new_capacity, self.sentinel, dtype=np.int32)
        v = np.full(new_capacity, self.sentinel, dtype=np.int32)
        u[: self.capacity] = self._u
        v[: self.capacity] = self._v
        # the new slots [capacity, new_capacity) extend the never-used
        # range [_fresh, capacity) as they are
        self._u, self._v = u, v
        self.capacity = new_capacity
        self.generation += 1
        self._version += 1

    def shrink_target(self) -> int | None:
        """Pow-2 capacity an epoch shrink would land on, or None.

        Hysteresis: only fires below ``SHRINK_FRACTION`` occupancy and the
        target keeps 2x headroom (next regrow needs the live set to double),
        so grow/shrink cannot oscillate on a stable graph."""
        if self.n_edges > self.capacity * SHRINK_FRACTION:
            return None
        target = max(next_pow2(2 * max(self.n_edges, 1)), self.min_capacity)
        return target if target < self.capacity else None

    def epoch_compact(self, shrink: bool = False) -> bool:
        """Rebuild a dense slot prefix (hole-free); with ``shrink=True``
        also drop to ``shrink_target()`` when the hysteresis allows. Called
        by the delta engine's epoch refresh; O(n_edges), amortized away by
        the epoch. Returns True when capacity changed."""
        with span("buffer_compact") as sp:
            new_capacity = self.capacity
            if shrink:
                target = self.shrink_target()
                if target is not None:
                    new_capacity = target
            if new_capacity != self.capacity:
                self._u = np.full(new_capacity, self.sentinel, np.int32)
                self._v = np.full(new_capacity, self.sentinel, np.int32)
            else:
                self._u.fill(self.sentinel)
                self._v.fill(self.sentinel)
            shrunk = new_capacity != self.capacity
            self.capacity = new_capacity
            # the sorted keys are the live pairs in (u, v) order: they
            # fill the dense prefix as they are
            n = self._keys.size
            self._u[:n], self._v[:n] = np.divmod(self._keys, self.n_nodes)
            self._kslot = np.arange(n, dtype=np.int32)
            self._fresh = n
            self._holes = np.empty(0, dtype=np.int32)
            self.generation += 1
            self._version += 1
            sp.set("n_edges", n).set("shrunk", shrunk)
        return shrunk

    # -- views --------------------------------------------------------------
    def host_view(self) -> tuple[np.ndarray, np.ndarray]:
        """(u, v) undirected slot arrays, shape [capacity], sentinel-padded
        — the zero-copy host input for candidate compaction (core/prune.py).
        Callers must treat the arrays as read-only."""
        return self._u, self._v

    def device_view(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) symmetric COO, shape [2 * capacity], sentinel-padded —
        drop-in for the ``Graph.src``/``Graph.dst`` convention. Holes carry
        the sentinel so every edge-masked reduction skips them for free."""
        src = np.concatenate([self._u, self._v])
        dst = np.concatenate([self._v, self._u])
        return src, dst

    def resident_state(self, node_capacity: int) -> tuple[
            np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, deg) — the exact device-resident state a full resync
        uploads: the symmetric COO view plus the int32 degree histogram over
        the (pow-2 padded) vertex space. One code path for both the
        per-tenant engine (``DeltaEngine._resync_device``) and the fused
        multi-tenant lane writes (stream/fused.py), so a fused lane's
        post-resync state is bit-identical to an unbatched engine's by
        construction. Pair it with ``generation`` to track lane staleness:
        a lane whose recorded generation trails the buffer's must re-upload
        through this view before the next fused program runs."""
        src, dst = self.device_view()
        valid = src[src < self.sentinel]
        deg = np.bincount(valid, minlength=node_capacity)
        return src, dst, deg[:node_capacity].astype(np.int32)

    def dst_sorted_state(self, node_capacity: int) -> tuple[
            np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, deg, lane_perm) — ``resident_state`` with the symmetric
        COO lanes stably sorted by dst, the layout the Pallas kernel tier's
        band-skip precondition wants (kernels/segsum.py). ``lane_perm[i]`` is
        the sorted position of unsorted lane ``i`` (slot ``s`` occupies lanes
        ``s`` and ``s + capacity``), so a delta engine can translate its
        O(batch) slot patches into the sorted layout without re-uploading.

        The tuple is a *snapshot*: cached until the next mutation, and
        mutations patched through ``lane_perm`` land at the snapshot's
        positions — the device copy drifts slightly out of sort order
        mid-epoch (harmless: sortedness is a kernel *performance*
        precondition, results stay bit-identical) and is repaired by the
        next resync, which re-sorts from the current host state. Sentinel
        (hole) lanes sort past every real vertex id, keeping the kernel's
        dense-band prefix tight."""
        key = (self._version, int(node_capacity))
        if self._sorted_cache is not None and self._sorted_cache[0] == key:
            return self._sorted_cache[1]
        src, dst, deg = self.resident_state(node_capacity)
        order = np.argsort(dst, kind="stable")
        lane_perm = np.empty(order.size, dtype=np.int32)
        lane_perm[order] = np.arange(order.size, dtype=np.int32)
        out = (np.ascontiguousarray(src[order]),
               np.ascontiguousarray(dst[order]), deg, lane_perm)
        self._sorted_cache = (key, out)
        return out

    def to_graph(self) -> Graph:
        """Materialize an immutable Graph (compacted) — the oracle view."""
        return Graph.from_edges(self.live_pairs(), n_nodes=self.n_nodes)

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"EdgeBuffer(|V|={self.n_nodes}, |E|={self.n_edges}, "
            f"capacity={self.capacity}, gen={self.generation})"
        )


__all__ = ["EdgeBuffer", "next_pow2", "MIN_CAPACITY", "SHRINK_FRACTION",
           "TOMBSTONE_COMPACT_FRACTION"]
