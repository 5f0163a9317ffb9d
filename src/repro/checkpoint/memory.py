"""Hot in-memory checkpoint tier with peer redundancy (survey §8.3.1,
Gemini / CheckFreq style).

The disk tier (:mod:`repro.checkpoint.store`) makes checkpoints *durable*;
this module makes the common-case restore *fast*. A
:class:`MemoryCheckpointTier` keeps a host-RAM ring of the last ``keep``
snapshots — same shard/manifest/digest schema as the disk tier (reusing its
``_flatten_with_names`` / ``_leaf_shards`` / ``_checksum`` / ``_crc32``
machinery), so a memory-tier entry is byte-equivalent to what the disk
persist would have written — and the recovery driver
(:func:`repro.ft.recovery.run_with_recovery`) restores **memory-tier first**,
falling back to the integrity-verified disk walk only when the hot tier
cannot serve (no entry, layout mismatch after a remesh, or shards lost
beyond repair).

Peer redundancy (the Gemini trick): RAM checkpoints die with their host, so
a bare in-memory ring protects against software faults (NaN rollback, SDC
rollback) but not machine loss. Each snapshot's shards are therefore
assigned a *home* group ``g`` (round-robin over ``groups`` logical
host-groups) and every group's shard buffers are additionally mirrored onto
its ring neighbor ``(g+1) % groups``. Losing one whole group
(:meth:`lose_group`, the simulated host failure) still leaves every shard
available — primaries on the survivors plus the lost group's bytes on its
neighbor's mirror — so :meth:`restore` rebuilds the full tree from RAM
without touching disk. Mirror-served shards are always digest-verified
(sha256-prefix + CRC32 + dtype/shape) before use; primary-served shards
skip re-verification by default — they were digested at save time and RAM
is assumed fault-free between save and restore, which is what makes the hot
path ~an order of magnitude faster than the verified disk walk.

On a real multi-host fleet the mirror exchange is a ring ``ppermute`` of
shard buffers across host groups (each host sends its shard bytes one hop
around the data-parallel ring while receiving its neighbor's); in this
single-process reproduction the rotation happens host-side with owned numpy
copies, which preserves the redundancy *semantics* — the mirror is a
physically distinct buffer that survives ``lose_group`` — while staying
runnable on one host.

Tiered restore order (driver's view):

1. memory tier, primary shards (fast path, no re-verify);
2. memory tier, peer rebuild (neighbor mirrors, digest-verified);
3. disk walk newest-first, skipping corrupt checkpoints (verified), via
   :meth:`CheckpointManager.restore` / ``restore_resharded`` for remesh.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from .store import (CorruptCheckpointError, _checksum, _crc32,
                    _flatten_with_names, _leaf_shards, _plan_meta, _span,
                    layout_diffs)


class MemoryCheckpointTier:
    """Host-RAM ring of the last ``keep`` snapshots with ring-neighbor
    shard mirroring.

    ``groups`` is the number of logical host-groups in the redundancy ring
    (on a fleet: one per host; here: a partition of the shard set). With
    ``peer_redundancy=False`` the mirror copies are skipped — half the RAM,
    no tolerance to :meth:`lose_group`.
    """

    def __init__(self, keep: int = 2, peer_redundancy: bool = True,
                 groups: int = 2, flight=None):
        self.keep = max(1, int(keep))
        self.peer_redundancy = bool(peer_redundancy)
        self.groups = max(1, int(groups))
        self.flight = flight
        self._ring: deque = deque(maxlen=self.keep)
        self.snapshot_seconds = 0.0   # last save() wall time
        self.restore_seconds = 0.0    # last restore() wall time
        self.last_rebuild = 0         # shards served from mirrors last restore

    # -- save ---------------------------------------------------------------

    def save(self, step: int, tree: Any, plan=None, mesh=None) -> None:
        """Snapshot ``tree`` into the RAM ring (blocking host copy).

        Builds the same manifest the disk tier would (per-shard key, global
        index slices, sha256-prefix, CRC32, dtype/shape) plus a ``home``
        group per shard, then rotates each group's buffers onto its ring
        neighbor's mirror. The oldest entry is evicted *before* the new one
        is built, so host RAM never holds ``keep + 1`` snapshots: at full
        model size that extra copy (twice the state with mirrors) is what
        exhausts a host's RAM. Spans: ``mem.copy`` (device to host),
        ``mem.checksum`` (the digests), ``mem.mirror`` (the ring rotation).
        """
        t0 = time.perf_counter()
        while len(self._ring) >= self.keep:
            self._ring.popleft()
        named = _flatten_with_names(tree)
        with _span("mem.copy", step) as copied:
            leaves = [_leaf_shards(x, copy=True) for _, x in named]
        primary: Dict[int, Dict[str, np.ndarray]] = \
            {g: {} for g in range(self.groups)}
        shard_meta: List[List[Dict[str, Any]]] = []
        counter = 0
        with _span("mem.checksum", step) as digest:
            for i, shards in enumerate(leaves):
                metas = []
                for j, (idx, a) in enumerate(shards):
                    key = f"a{i}" if len(shards) == 1 else f"a{i}_s{j}"
                    home = counter % self.groups
                    counter += 1
                    primary[home][key] = a
                    metas.append({"key": key, "index": idx,
                                  "checksum": _checksum(a),
                                  "crc32": _crc32(a),
                                  "dtype": str(a.dtype),
                                  "shape": [int(d) for d in a.shape],
                                  "home": home})
                shard_meta.append(metas)
        manifest = {
            "step": int(step),
            "names": [n for n, _ in named],
            "shapes": [[int(d) for d in np.shape(x)] for _, x in named],
            "dtypes": [m[0]["dtype"] for m in shard_meta],
            "shards": shard_meta,
            "plan": _plan_meta(plan),
            "mesh_axes": dict(mesh.shape) if mesh is not None else None,
            "time": time.time(),
        }
        mirror: Dict[int, Dict[str, np.ndarray]] = \
            {g: {} for g in range(self.groups)}
        with _span("mem.mirror", step) as rotate:
            if self.peer_redundancy and self.groups > 1:
                # ring rotation: group g's bytes also live on (g+1) % groups
                # — host-side stand-in for the fleet's ring ppermute of
                # shard buffers (owned copies, so they survive lose_group(g))
                for g in range(self.groups):
                    dst = (g + 1) % self.groups
                    for key, a in primary[g].items():
                        mirror[dst][key] = np.array(a, copy=True)
        self._ring.append({"manifest": manifest, "primary": primary,
                           "mirror": mirror})
        self.snapshot_seconds = time.perf_counter() - t0
        if self.flight is not None:
            self.flight.record("ckpt.persist", step, tier="memory",
                               seconds=self.snapshot_seconds,
                               copy_seconds=copied.seconds,
                               checksum_seconds=digest.seconds,
                               mirror_seconds=rotate.seconds,
                               groups=self.groups,
                               mirrored=self.peer_redundancy)

    # -- introspection ------------------------------------------------------

    def steps(self, newest_first: bool = False) -> List[int]:
        out = sorted(e["manifest"]["step"] for e in self._ring)
        return out[::-1] if newest_first else out

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def clear(self) -> None:
        """Drop every entry — required after a remesh (recorded layouts no
        longer match) and on preemption exit (RAM dies with the process)."""
        self._ring.clear()

    def _entry(self, step: Optional[int]) -> Dict[str, Any]:
        if not self._ring:
            raise CorruptCheckpointError("memory tier is empty")
        if step is None:
            return self._ring[-1]
        for e in self._ring:
            if e["manifest"]["step"] == step:
                return e
        raise CorruptCheckpointError(f"step {step} not in memory tier "
                                     f"(have {self.steps()})")

    # -- fault simulation ---------------------------------------------------

    def lose_group(self, g: int) -> int:
        """Simulate losing host-group ``g``: drop its primary shards *and*
        the mirror bytes it was holding for its neighbor, across every ring
        entry. Returns the number of shard buffers destroyed."""
        lost = 0
        for e in self._ring:
            lost += len(e["primary"].get(g, {}))
            lost += len(e["mirror"].get(g, {}))
            e["primary"][g] = {}
            e["mirror"][g] = {}
        if self.flight is not None:
            self.flight.record("mem.lost_group",
                               self.latest_step() or -1,
                               group=int(g), shards_lost=lost)
        return lost

    # -- restore ------------------------------------------------------------

    def _fetch(self, e: Dict[str, Any], m: Dict[str, Any],
               verify: bool) -> np.ndarray:
        """One shard's bytes: primary first, neighbor mirror on miss.

        Mirror hits are always digest-verified — rebuilt bytes crossed a
        (simulated) network hop and a host loss, so they must prove
        themselves; primary hits trust the save-time digests unless
        ``verify`` asks otherwise.
        """
        home = m.get("home", 0)
        a = e["primary"].get(home, {}).get(m["key"])
        from_mirror = False
        if a is None:
            a = e["mirror"].get((home + 1) % self.groups, {}).get(m["key"])
            from_mirror = True
            if a is None:
                raise CorruptCheckpointError(
                    f"shard {m['key']} lost from memory tier (home group "
                    f"{home} and its mirror both gone)")
        if verify or from_mirror:
            if _checksum(a) != m["checksum"] or _crc32(a) != m["crc32"]:
                raise CorruptCheckpointError(
                    f"memory-tier digest mismatch for shard {m['key']}")
            if str(a.dtype) != m["dtype"] or list(a.shape) != m["shape"]:
                raise CorruptCheckpointError(
                    f"memory-tier dtype/shape mismatch for shard {m['key']}")
        if from_mirror:
            self.last_rebuild += 1
        return a

    def restore(self, tree_like: Any, step: Optional[int] = None,
                plan=None, mesh=None, verify: bool = False
                ) -> Tuple[int, Any]:
        """Restore into the structure of ``tree_like``; returns (step, tree).

        Raises :class:`CorruptCheckpointError` when the tier cannot serve
        (empty, step missing, shards lost beyond the mirror) and
        ``ValueError`` on a layout mismatch (e.g. after a remesh) — the
        recovery driver catches both and falls to the disk walk.
        ``self.last_rebuild`` reports how many shards came from peer
        mirrors (0 ⇒ pure fast path). Spans, one of each per leaf:
        ``mem.fetch`` (the shards' host bytes, mirror rebuild and digest
        checks included) and ``mem.put`` (host to device).
        """
        t0 = time.perf_counter()
        self.last_rebuild = 0
        e = self._entry(step)
        man = e["manifest"]
        diffs = layout_diffs(man, plan, mesh)
        if diffs:
            raise ValueError(
                f"memory-tier layout mismatch (recorded != requested): "
                f"{diffs} — remesh restores go through the disk tier")
        named = _flatten_with_names(tree_like)
        assert [n for n, _ in named] == man["names"], \
            "memory checkpoint tree structure mismatch"
        parts: Dict[str, float] = {}       # seconds of each span, summed
        leaves = []
        for metas, shape, dt, (_, l) in zip(
                man["shards"], man["shapes"], man["dtypes"], named):
            with _span("mem.fetch", man["step"], into=parts):
                if len(metas) == 1:
                    full = self._fetch(e, metas[0], verify)
                else:
                    full = np.zeros(shape, dtype=np.dtype(dt))
                    for m in metas:
                        sl = tuple(slice(a, b) for a, b in m["index"])
                        full[sl] = self._fetch(e, m, verify)
            with _span("mem.put", man["step"], into=parts):
                arr = jax.numpy.asarray(full, dtype=getattr(l, "dtype", None)
                                        or full.dtype)
                if isinstance(l, jax.Array) and getattr(l, "committed",
                                                        False):
                    arr = jax.device_put(arr, l.sharding)
            leaves.append(arr)
        treedef = jax.tree_util.tree_structure(tree_like)
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        self.restore_seconds = time.perf_counter() - t0
        if self.flight is not None:
            self.flight.record("mem.restore", man["step"],
                               rebuilt_shards=self.last_rebuild,
                               seconds=self.restore_seconds,
                               fetch_seconds=parts.get("mem.fetch", 0.0),
                               put_seconds=parts.get("mem.put", 0.0))
        return man["step"], tree
