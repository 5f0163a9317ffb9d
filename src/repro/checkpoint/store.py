"""Checkpointing (survey §8.3) — shard-aware save, async snapshots, and
elastic cross-mesh restore.

Persistent checkpoints follow the snapshot/persist split of §8.3.1:

- ``snapshot``: device -> host copy (the only phase that can stall training);
- ``persist``: host -> disk write, runs on a background thread
  (snapshot-stall checkpointing à la Check-N-Run/MegaScale).

With ``async_snapshot=True`` the snapshot itself is double-buffered
(§8.3.1 snapshot-stall elimination): ``save`` only *dispatches* a device-side
clone of the state (one jitted copy per tree layout, asynchronously executed,
sharding-preserving) and returns; the device->host copy and the disk write
both run on the background thread against the clone. The clone is what makes
this safe — the training loop is free to donate the live state's buffers into
the next step while the copy drains (``np.asarray`` of a CPU shard is a
zero-copy *view* of the device buffer, so snapshotting the live state without
a clone would race donation). Cost: transiently one extra copy of the state
in device memory (the classic double buffer). ``wait()`` is the completion
fence — ``save`` calls it first, so at most one snapshot+persist is in
flight — and any failure on the background thread (full disk, revoked
directory) is re-raised at the next ``save()``/``wait()`` instead of dying
silently with the thread.

Layout: one ``.npz`` per checkpoint plus a JSON manifest carrying the step,
the flattened tree structure and integrity checksums.

Shard-aware (survey §3.3.1: a designated worker per group writes its shard):
the snapshot phase walks ``jax.Array.addressable_shards`` and copies each
*unique* device shard to host instead of gathering the full array — under
cp/tp/ZeRO meshes the device→host copy moves 1/shards of the bytes and the
replicated copy never materializes. The manifest records each shard's
global-index slices plus the :class:`repro.core.config.ParallelPlan` axes
(``tp``/``cp``/``pp``/``dp_shard``/``zero_stage``/impl knobs) and mesh axis
sizes.

Restore is **elastic** (survey §8.3.2, the cloud-native resumable-on-a-
different-cluster gap): because the manifest records every shard's global
index slices, a checkpoint written on one mesh can be reassembled into full
arrays and *re-sliced* onto any other layout — fewer hosts after a failure,
more after repair. :meth:`CheckpointManager.check_plan` is the router:
``"replay"`` when the requested ParallelPlan layout axes and mesh axis sizes
match the recorded ones (fast shard-to-shard :meth:`restore`), ``"reshard"``
when they differ and ``elastic=True`` (take
:meth:`restore_resharded`, which re-places every leaf — params *and* the
ZeRO-1 optimizer moment shards, which land re-scattered over the new data
axis — with explicitly computed target shardings). A mismatch without
``elastic`` still refuses, because silently replaying a shard-written
checkpoint onto a different layout is the §8 failure mode this module
exists to prevent.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
import zipfile
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class CorruptCheckpointError(IOError):
    """A checkpoint on disk failed integrity verification (checksum/CRC32
    mismatch, unreadable manifest, missing or truncated shard file).

    Subclasses ``IOError`` so callers matching the historical checksum
    failure keep working; ``ft/recovery`` catches it specifically to fall
    back to the newest *intact* checkpoint instead of crashing.
    """


def _inject():
    """The fault-injection module, or None before repro.ft is importable.

    Lazy by necessity: ``repro.ft.__init__`` imports ``ft.recovery`` which
    imports this module — a top-level import here would cycle.
    """
    try:
        from repro.ft import inject  # noqa: PLC0415
        return inject
    except ImportError:              # pragma: no cover - partial installs
        return None


def _span(name: str, step: int, into: Optional[Dict[str, float]] = None):
    """A :class:`repro.ft.flight.span` (imported on use, as in
    :func:`_inject`: ``repro.ft`` imports this module)."""
    from repro.ft.flight import span  # noqa: PLC0415
    return span(name, step, into=into)

# the ParallelPlan fields recorded in the manifest (impl/schedule knobs ride
# along for forensics) ...
PLAN_AXES = ("tp", "tp_impl", "cp", "cp_impl", "dp_shard", "zero_stage",
             "ep", "ep_impl", "pp", "pp_schedule", "pp_layout")
# ... and the subset check_plan actually compares: only the axes that change
# how saved state maps onto devices. A pure schedule/impl change
# (gpipe→1f1b, gather→ring) is replay-safe — restore reassembles full
# arrays and re-places them — so it must not be refused. pp_layout IS
# compared: a Malleus rebalance changes which layers live on which stage,
# so under elastic restore it routes "reshard", never a refusal.
PLAN_LAYOUT_AXES = ("tp", "cp", "dp_shard", "zero_stage", "ep", "pp",
                    "pp_layout")


def _plan_meta(plan) -> Optional[Dict[str, Any]]:
    if plan is None:
        return None
    d = dataclasses.asdict(plan)
    # tuples (pp_layout) JSON-round-trip as lists; normalize at record time
    # so manifest-vs-plan comparisons in layout_diffs stay type-stable
    return {k: list(d[k]) if isinstance(d[k], tuple) else d[k]
            for k in PLAN_AXES if k in d}


def layout_diffs(manifest: Dict[str, Any], plan, mesh=None
                 ) -> Dict[str, Tuple[Any, Any]]:
    """Layout-axis differences between a manifest and a requested plan/mesh.

    Empty dict ⇒ shard-to-shard replay is safe. Shared by
    :meth:`CheckpointManager.check_plan` (disk tier) and
    :class:`repro.checkpoint.memory.MemoryCheckpointTier` (hot tier), so
    both tiers route replay/reshard/refuse with identical rules.
    """
    recorded = manifest.get("plan")
    diffs: Dict[str, Tuple[Any, Any]] = {}
    if recorded is not None and plan is not None:
        want = _plan_meta(plan)
        rec = dict(recorded)
        # manifests written before ep became an integer degree recorded the
        # legacy bool: False means "no EP" (degree 1); True (GSPMD expert
        # sharding) has no degree equivalent and never replays onto the new
        # folded layouts (Python would otherwise equate True == 1)
        if isinstance(rec.get("ep"), bool):
            rec["ep"] = 1 if rec["ep"] is False else "legacy-gspmd-ep"
        diffs = {k: (rec[k], want[k]) for k in PLAN_LAYOUT_AXES
                 if k in rec and k in want and rec[k] != want[k]}
    rec_mesh = manifest.get("mesh_axes")
    if mesh is not None and rec_mesh is not None:
        want_mesh = {k: int(v) for k, v in dict(mesh.shape).items()}
        if {k: int(v) for k, v in rec_mesh.items()} != want_mesh:
            diffs["mesh_axes"] = (rec_mesh, want_mesh)
    return diffs


def _index_json(index: Tuple[slice, ...], shape) -> List[List[int]]:
    """A shard's global-index slices as JSON: [[start, stop], ...]."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def _leaf_shards(x, copy: bool = True) -> List[Tuple[List[List[int]], np.ndarray]]:
    """Unique (index, host copy) pairs for one leaf.

    jax.Arrays snapshot per addressable shard (replicas deduped by index);
    anything else (numpy, python scalars) is a single whole-array shard.
    ``copy=True`` forces an owned host buffer — ``np.asarray`` of a CPU
    shard is a zero-copy view of the device buffer, which a later donation
    of that buffer would invalidate under the persist thread. Snapshots of a
    manager-owned clone pass ``copy=False`` (the clone outlives the persist).
    """
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        if not x.is_fully_addressable:
            # multi-process meshes: this process sees only its own shards;
            # recording a partial shard list and zero-filling the rest at
            # restore would be silent corruption — fail loudly (the
            # multi-host per-writer layout is future work)
            raise ValueError(
                "sharded checkpoint save requires fully-addressable arrays; "
                "multi-process meshes need a per-host writer rank")
        seen: Dict[Tuple, Tuple[List[List[int]], np.ndarray]] = {}
        for sh in x.addressable_shards:
            idx = _index_json(tuple(sh.index), x.shape)
            key = tuple(map(tuple, idx))
            if key not in seen:
                host = np.asarray(sh.data)
                seen[key] = (idx, np.array(host, copy=True) if copy else host)
        return list(seen.values())
    arr = np.asarray(x)
    return [(_index_json(tuple(slice(0, d) for d in arr.shape), arr.shape),
             arr)]


def _flatten_with_names(tree: Any) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(
            str(p.key) if isinstance(p, jax.tree_util.DictKey)
            else str(getattr(p, "name", getattr(p, "idx", p)))
            for p in path)
        out.append((name, leaf))
    return out


def _checksum(arr: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


def _crc32(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _clone_shardings(leaves: List[Any]):
    """Per-leaf out_shardings for the snapshot clone.

    Committed arrays keep their own sharding. Uncommitted leaves (scalars on
    the default device) are normalized onto the committed leaves' mesh,
    replicated — a mixed device assignment would be rejected by jit, and a
    mesh-replicated clone persists byte-identically (replicas dedup to one
    full-coverage shard).
    """
    from jax.sharding import NamedSharding, PartitionSpec  # noqa: PLC0415
    meshes = {l.sharding.mesh for l in leaves
              if getattr(l, "committed", False)
              and isinstance(l.sharding, NamedSharding)}
    mesh = meshes.pop() if len(meshes) == 1 else None
    out = []
    for l in leaves:
        if getattr(l, "committed", False) or mesh is None:
            out.append(l.sharding)
        else:
            out.append(NamedSharding(mesh, PartitionSpec()))
    return out


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3,
                 async_persist: bool = True, async_snapshot: bool = False,
                 io_retries: int = 3, io_backoff: float = 0.05,
                 io_timeout: float = 30.0, flight=None):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        # optional repro.ft.flight.FlightRecorder — persist/GC events land in
        # the crash black box (deque appends are thread-safe, so logging from
        # the persist thread is fine)
        self.flight = flight
        self.async_persist = async_persist
        self.async_snapshot = async_snapshot
        # persist-I/O robustness: ``io_retries`` attempts with exponential
        # backoff starting at ``io_backoff`` seconds, abandoned once the
        # cumulative wait would pass ``io_timeout`` (a wedged filesystem must
        # not hold the fence forever). Exhausted retries surface through
        # save()/wait() — ft/recovery records them as a "ckpt_io" anomaly.
        self.io_retries = max(1, int(io_retries))
        self.io_backoff = io_backoff
        self.io_timeout = io_timeout
        self._pending: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._snapshot_ref: Any = None        # device clone kept alive
        self._clone_cache: Dict[Tuple, Callable] = {}
        self.snapshot_seconds = 0.0           # main-thread stall of last save
        self.d2h_seconds = 0.0                # device->host copy (wherever it ran)
        self.persist_seconds = 0.0

    # -- save ---------------------------------------------------------------

    def _cloner(self, leaves: List[Any]) -> Optional[List[Any]]:
        """Device-side clone of the whole tree (the double buffer).

        One jitted sharding-preserving copy per tree layout — a single async
        dispatch, so the main-thread stall is sub-millisecond regardless of
        state size. Returns the cloned leaves, or None when the leaf mix
        can't be cloned on device (e.g. committed arrays pinned to
        incompatible device sets) — the caller falls back to the blocking
        host-copy snapshot.
        """
        jaxish = [isinstance(l, jax.Array) and not isinstance(l, jax.core.Tracer)
                  for l in leaves]
        arrs = [l for l, j in zip(leaves, jaxish) if j]
        if not arrs:
            return None
        key = tuple((a.shape, str(a.dtype), a.sharding) for a in arrs)
        fn = self._clone_cache.get(key)
        if fn is None:
            try:
                jitted = jax.jit(lambda ls: [jnp.copy(l) for l in ls],
                                 out_shardings=_clone_shardings(arrs))
                jax.block_until_ready(jitted(arrs))   # compile + validate now
            except Exception:
                return None
            fn = self._clone_cache[key] = jitted
        cloned_arrs = fn(arrs)
        it = iter(cloned_arrs)
        # non-jax leaves (numpy, python scalars) are tiny: owned-copy inline
        return [next(it) if j else np.array(np.asarray(l), copy=True)
                for l, j in zip(leaves, jaxish)]

    def save(self, step: int, tree: Any, blocking: bool = False,
             plan=None, mesh=None) -> Path:
        """Snapshot then persist; returns the checkpoint path (sans suffix).

        The snapshot copies each leaf's unique *addressable shards* to host
        (no full-array gather). With ``async_snapshot`` the main thread only
        dispatches a device-side clone (double buffer) and the host copy
        overlaps subsequent train steps; otherwise the host copy is the
        stall. ``blocking=True`` forces everything inline. ``plan``/``mesh``
        record the layout axes in the manifest so replay/reshard can route.
        Raises any failure from the *previous* save's background work.
        """
        self.wait()                                      # fence + raise errors
        with _span("ckpt.snapshot", step) as snap:
            named = _flatten_with_names(tree)
            names = [n for n, _ in named]
            cloned = None
            if self.async_snapshot and not blocking:
                cloned = self._cloner([x for _, x in named])
            # double-buffer path: the stall is flatten + clone dispatch only
            host = (None if cloned is not None
                    else [(n, _leaf_shards(x)) for n, x in named])
        self.snapshot_seconds = snap.seconds

        path = self.dir / f"ckpt_{step:08d}"
        mesh_axes = dict(mesh.shape) if mesh is not None else None
        plan_meta = _plan_meta(plan)
        shapes = [[int(d) for d in np.shape(x)] for _, x in named]
        self._snapshot_ref = cloned                      # keep clone alive

        def _snapshot_and_persist():
            nonlocal host
            if host is None:
                with _span("ckpt.snapshot", step) as d2h:
                    host = [(n, _leaf_shards(x, copy=False))
                            for n, x in zip(names, cloned)]
                self.d2h_seconds = d2h.seconds
            t1 = time.perf_counter()
            arrays = {}
            shard_meta = []
            with _span("ckpt.checksum", step) as digest:
                for i, (_, shards) in enumerate(host):
                    keys = []
                    for j, (idx, a) in enumerate(shards):
                        # single-shard leaves keep the legacy "a{i}" key
                        key = f"a{i}" if len(shards) == 1 else f"a{i}_s{j}"
                        arrays[key] = a
                        # sha256 prefix (legacy) + CRC32 + dtype/shape
                        # digests: restore verifies all of them, so a
                        # flipped bit, a truncated member, or a silently
                        # retyped array all surface as CorruptCheckpointError
                        keys.append({"key": key, "index": idx,
                                     "checksum": _checksum(a),
                                     "crc32": _crc32(a),
                                     "dtype": str(a.dtype),
                                     "shape": [int(d) for d in a.shape]})
                    shard_meta.append(keys)
            manifest = {
                "step": step,
                "names": names,
                "checksums": [m[0]["checksum"] for m in shard_meta],
                "dtypes": [str(a.dtype) for _, ss in host for _, a in ss[:1]],
                "shapes": shapes,
                "shards": shard_meta,
                "plan": plan_meta,
                "mesh_axes": mesh_axes,
                "time": time.time(),
            }
            with _span("ckpt.write", step):
                self._persist_with_retry(step, path, arrays, manifest)
            self.persist_seconds = time.perf_counter() - t1
            if self.flight is not None:
                self.flight.record("ckpt.persist", step, tier="disk",
                                   seconds=self.persist_seconds,
                                   snapshot_seconds=self.snapshot_seconds,
                                   checksum_seconds=digest.seconds)
            self._gc()

        def _bg():
            try:
                _snapshot_and_persist()
            except BaseException as e:  # surfaced at next save()/wait()
                self._error = e
            finally:
                self._snapshot_ref = None                # free the clone

        if (self.async_persist or cloned is not None) and not blocking:
            self._pending = threading.Thread(target=_bg, daemon=True)
            self._pending.start()
        else:
            try:
                _snapshot_and_persist()
            finally:
                self._snapshot_ref = None
        return path

    def _persist_once(self, step: int, path: Path, arrays, manifest) -> None:
        """One atomic persist attempt: npz then manifest, each written to a
        temp path and ``os.replace``d into place. The npz lands first — a
        crash between the two leaves no manifest, so the half-written
        checkpoint is never listed, let alone picked as latest. The
        ``ckpt.persist`` fault point fires per attempt (hang /
        persist_exc); ``ckpt.shard_write`` fires *after* a
        successful-looking write (silent corruption: the shard file is
        dropped or truncated but the writer saw no error)."""
        inj = _inject()
        if inj is not None:
            inj.io_fault("ckpt.persist", step)
        tmp_npz = str(path) + ".tmp.npz"          # savez appends .npz itself
        np.savez(tmp_npz[:-4], **arrays)
        os.replace(tmp_npz, str(path) + ".npz")
        tmp_json = Path(str(path) + ".json.tmp")
        tmp_json.write_text(json.dumps(manifest))
        os.replace(tmp_json, path.with_suffix(".json"))
        if inj is not None:
            sp = inj.io_spec_for("ckpt.shard_write", step,
                                 ("drop_write", "truncate_write"))
            if sp is not None:
                npz = Path(str(path) + ".npz")
                if sp.kind == "drop_write":
                    npz.unlink(missing_ok=True)
                else:
                    data = npz.read_bytes()
                    npz.write_bytes(data[:max(len(data) // 2, 1)])

    def _persist_with_retry(self, step: int, path: Path, arrays,
                            manifest) -> None:
        """Exponential-backoff retry around the persist write: transient I/O
        errors (NFS blips, injected persist_exc) are retried up to
        ``io_retries`` times with delays ``io_backoff * 2^k``, bounded by the
        cumulative ``io_timeout`` deadline; the final failure propagates."""
        deadline = time.perf_counter() + self.io_timeout
        delay = self.io_backoff
        for attempt in range(1, self.io_retries + 1):
            try:
                return self._persist_once(step, path, arrays, manifest)
            except Exception as e:
                if (attempt >= self.io_retries
                        or time.perf_counter() + delay > deadline):
                    if self.flight is not None:
                        self.flight.record("ckpt.persist_fail", step,
                                           attempts=attempt, error=repr(e))
                    raise
                time.sleep(delay)
                delay *= 2

    def wait(self):
        """Completion fence: join in-flight snapshot/persist work and raise
        any failure it hit (a persist that dies with its daemon thread would
        otherwise be mistaken for a durable checkpoint)."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                f"background checkpoint persist failed: {err!r}") from err

    def _is_intact(self, step: int) -> bool:
        """Structural intactness: manifest parses, the npz zip container
        opens, and every recorded shard member is present. Catches dropped
        and truncated shard writes (a truncated zip loses its end-of-file
        central directory) without re-reading shard bytes — cheap enough to
        run per GC pass. Bit flips inside a member are left to the full
        checksum verify at restore time. No fence: also called from the
        persist thread by :meth:`_gc` (``wait()`` there would join the
        thread into itself)."""
        path = self.dir / f"ckpt_{step:08d}"
        try:
            man = self._read_manifest(step)
            with zipfile.ZipFile(str(path) + ".npz") as zf:
                members = set(zf.namelist())
            shard_meta = man.get("shards")
            if shard_meta is None:            # legacy single-array layout
                shard_meta = [[{"key": f"a{i}"}]
                              for i in range(len(man["checksums"]))]
            for metas in shard_meta:
                for m in metas:
                    if m["key"] + ".npy" not in members:
                        return False
            return True
        except (CorruptCheckpointError, OSError, zipfile.BadZipFile,
                KeyError, ValueError):
            return False

    def _gc(self):
        """Evict checkpoints beyond ``keep`` — verify-before-evict.

        Age alone is not a safe eviction key: corrupt checkpoints (dropped /
        truncated shard writes that looked successful) count toward ``keep``,
        so a burst of bad persists used to GC every *restorable* checkpoint
        while keeping only wreckage. Now, if none of the kept (newest
        ``keep``) checkpoints is structurally intact, the newest intact
        candidate among the evictees is spared — a keep-floor of one
        restorable checkpoint whenever one exists. Runs on the persist
        thread, so it must never call :meth:`wait`."""
        steps = []
        for p in self.dir.glob("ckpt_*.json"):
            try:
                steps.append(int(p.stem.split("_", 1)[1]))
            except (IndexError, ValueError):
                continue
        steps.sort()
        doomed = steps[:-self.keep] if self.keep > 0 else list(steps)
        if not doomed:
            return
        spare = None
        if not any(self._is_intact(s) for s in steps[len(doomed):]):
            for s in reversed(doomed):
                if self._is_intact(s):
                    spare = s
                    break
        for s in doomed:
            if s == spare:
                if self.flight is not None:
                    self.flight.record("ckpt.gc_spared", s,
                                       reason="newest_intact_keep_floor")
                continue
            old = self.dir / f"ckpt_{s:08d}.json"
            old.unlink(missing_ok=True)
            old.with_suffix(".npz").unlink(missing_ok=True)

    # -- restore --------------------------------------------------------------

    def steps(self, newest_first: bool = False) -> List[int]:
        """Steps of every checkpoint on disk, parsed from the *filenames*
        (never the manifest contents, so a corrupted JSON still lists and
        can be skipped by a fallback restore)."""
        self.wait()
        out = []
        for p in self.dir.glob("ckpt_*.json"):
            try:
                out.append(int(p.stem.split("_", 1)[1]))
            except (IndexError, ValueError):
                continue
        out.sort(reverse=newest_first)
        return out

    def latest_step(self) -> Optional[int]:
        steps = self.steps()
        return steps[-1] if steps else None

    def _read_manifest(self, step: int) -> Dict[str, Any]:
        """Manifest JSON for an explicit step — no completion fence, so it
        is safe from the persist thread (:meth:`_is_intact`/:meth:`_gc`)."""
        path = self.dir / f"ckpt_{step:08d}"
        try:
            return json.loads(path.with_suffix(".json").read_text())
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            # json.JSONDecodeError subclasses ValueError — without this wrap
            # it would be mistaken for check_plan's layout-mismatch error
            raise CorruptCheckpointError(
                f"unreadable manifest for step {step} in {self.dir}: "
                f"{e!r}") from e

    def manifest(self, step: Optional[int] = None) -> Dict[str, Any]:
        """The JSON manifest of a checkpoint (layout metadata included)."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        return self._read_manifest(step)

    def check_plan(self, plan, step: Optional[int] = None, *,
                   mesh=None, elastic: bool = False) -> str:
        """Route a restore: ``"replay"`` or ``"reshard"``.

        Compares the checkpoint's recorded ParallelPlan layout axes (and,
        when ``mesh`` is given, the mesh axis sizes) against the requested
        ones. Matching layouts replay shard-to-shard. Differing layouts
        return ``"reshard"`` when ``elastic=True`` — take
        :meth:`restore_resharded` — and raise ``ValueError`` otherwise:
        replaying a shard-written checkpoint onto a different cp/tp/dp
        layout silently reshards, which is exactly the failure mode a
        non-elastic ft/recovery must refuse.
        """
        man = self.manifest(step)
        diffs = layout_diffs(man, plan, mesh)
        if not diffs:
            return "replay"
        if elastic:
            return "reshard"
        raise ValueError(
            f"checkpoint layout mismatch (recorded != requested): {diffs}")

    def _load_full(self, step: Optional[int], verify: bool
                   ) -> Tuple[int, Dict[str, Any], List[np.ndarray]]:
        """Reassemble every leaf into a full host array from its recorded
        shard slices; returns (step, manifest, arrays)."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        return self._read_full(step, verify)

    def _read_full(self, step: int, verify: bool
                   ) -> Tuple[int, Dict[str, Any], List[np.ndarray]]:
        """:meth:`_load_full` minus the fence and step resolution — usable
        where ``wait()`` is illegal (persist thread) or already done."""
        path = self.dir / f"ckpt_{step:08d}"
        manifest = self._read_manifest(step)
        try:
            data = np.load(str(path) + ".npz")
        except (OSError, ValueError, zipfile.BadZipFile) as e:
            # missing / truncated / corrupted zip container
            raise CorruptCheckpointError(
                f"unreadable shard file {path}.npz: {e!r}") from e
        shard_meta = manifest.get("shards")
        if shard_meta is None:                # legacy single-array layout
            shard_meta = [[{"key": f"a{i}", "index": None, "checksum": c}]
                          for i, c in enumerate(manifest["checksums"])]
        arrays = []
        for metas, shape, dt, n in zip(
                shard_meta, manifest["shapes"], manifest["dtypes"],
                manifest["names"]):
            for m in metas:
                try:
                    a = data[m["key"]]
                except Exception as e:        # truncated/dropped zip member
                    raise CorruptCheckpointError(
                        f"unreadable shard {m['key']} for {n} in "
                        f"{path}: {e!r}") from e
                if not verify:
                    continue
                if _checksum(a) != m["checksum"] or \
                        ("crc32" in m and _crc32(a) != m["crc32"]):
                    raise CorruptCheckpointError(
                        f"checksum mismatch for {n} in {path}")
                if "dtype" in m and str(a.dtype) != m["dtype"]:
                    raise CorruptCheckpointError(
                        f"dtype digest mismatch for {n} in {path}: "
                        f"{a.dtype} != {m['dtype']}")
                if "shape" in m and list(a.shape) != list(m["shape"]):
                    raise CorruptCheckpointError(
                        f"shape digest mismatch for {n} in {path}: "
                        f"{list(a.shape)} != {m['shape']}")
            if len(metas) == 1:
                # one unique shard ⇒ it covers the whole array (a valid
                # sharding's shards union to the full index space)
                arrays.append(data[metas[0]["key"]])
                continue
            full = np.zeros(shape, dtype=np.dtype(dt))
            for m in metas:
                sl = tuple(slice(a, b) for a, b in m["index"])
                full[sl] = data[m["key"]]
            arrays.append(full)
        return step, manifest, arrays

    def restore(self, tree_like: Any, step: Optional[int] = None,
                verify: bool = True) -> Tuple[int, Any]:
        """Restore into the structure of ``tree_like``; returns (step, tree).

        Shards are reassembled by their recorded index slices; leaves whose
        ``tree_like`` twin carries a sharding are re-placed with it
        (device_put), so a cp/tp-sharded state restores shard-to-shard.
        """
        step, manifest, arrays = self._load_full(step, verify)
        named = _flatten_with_names(tree_like)
        assert [n for n, _ in named] == manifest["names"], \
            "checkpoint tree structure mismatch"
        leaves = []
        for a, (_, l) in zip(arrays, named):
            arr = jax.numpy.asarray(a, dtype=l.dtype)
            # re-place committed leaves on their recorded layout; an
            # uncommitted leaf (e.g. the scalar opt step) stays uncommitted —
            # committing it to one device would conflict with mesh-committed
            # siblings inside the jitted step
            if isinstance(l, jax.Array) and getattr(l, "committed", False):
                arr = jax.device_put(arr, l.sharding)
            leaves.append(arr)
        treedef = jax.tree_util.tree_structure(tree_like)
        return step, jax.tree_util.tree_unflatten(treedef, leaves)

    def restore_resharded(self, tree_like: Any, shardings: Any = None,
                          step: Optional[int] = None, verify: bool = True
                          ) -> Tuple[int, Any]:
        """Elastic restore onto a *different* mesh layout (survey §8.3.2).

        Full arrays are reassembled from the manifest's global-index shard
        slices — written on whatever mesh the checkpoint came from — and
        every leaf is re-sliced onto the target layout: ``shardings`` is a
        pytree (same structure as ``tree_like``) of target shardings, e.g.
        :func:`repro.core.sharding.train_state_shardings` under the new
        plan/mesh, which re-scatters the ZeRO-1 optimizer moment shards over
        the new data axis and re-shards tp/cp params onto the new model
        axes. Leaves whose ``shardings`` entry is None fall back to the
        ``tree_like`` twin's own sharding (matching :meth:`restore`).
        Returns (step, tree) with every leaf device_put on the target.
        """
        step, manifest, arrays = self._load_full(step, verify)
        named = _flatten_with_names(tree_like)
        assert [n for n, _ in named] == manifest["names"], \
            "checkpoint tree structure mismatch"
        treedef = jax.tree_util.tree_structure(tree_like)
        if shardings is None:
            target = [None] * len(named)
        else:
            target = treedef.flatten_up_to(shardings)
        leaves = []
        for a, (_, l), s in zip(arrays, named, target):
            arr = jax.numpy.asarray(a, dtype=getattr(l, "dtype", None) or a.dtype)
            if s is None and isinstance(l, jax.Array) \
                    and getattr(l, "committed", False):
                s = l.sharding      # same committed-only rule as restore()
            if s is not None:
                arr = jax.device_put(arr, s)
            leaves.append(arr)
        return step, jax.tree_util.tree_unflatten(treedef, leaves)
