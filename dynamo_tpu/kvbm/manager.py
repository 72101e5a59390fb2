"""KvBlockManager: tier policy + the engine connector.

Reference: lib/llm/src/block_manager.rs (KvBlockManager :99) and
block_manager/offload.rs (OffloadManager). The reference offloads a block
down the G1->G2->G3 chain when it is *registered* (hash bound); onboarding
walks the chain upward on a prefix-cache lookup miss. We do the same, but
the data path is a PIPELINE (docs/kvbm.md), not a sequence of inline
copies:

  * offload is WRITE-THROUGH at block-commit time, BATCHED per engine
    step: every `_commit_blocks` in a step stages its (hash, page) pairs;
    the engine's end-of-step `flush_step()` submits ONE `extract_pages`
    gather for all of them onto the serial device executor. Because every
    later write to those pages is itself a device op queued behind ours on
    the same executor, the gather always reads the pre-eviction contents —
    no device read-back is ever needed at eviction time (the reference
    needs its CUDA block_copy.cu + bounce buffers for this; XLA gather +
    serialized execution makes it free of synchronization hazards). The
    gather job only DISPATCHES (XLA execution is async); the device->host
    copy, the G2 store, and any G2->G3 cascade + file I/O run on a
    dedicated `kvbm-tier` thread, so the device executor loses only the
    dispatch microseconds per step.
  * the staged->stored path is a BOUNDED queue: when the tier thread falls
    behind, the OLDEST in-flight batch is dropped (blocks are unreferenced
    cache copies — dropping loses a future cache hit, never correctness)
    rather than stalling the step loop; drops are counted.
  * onboard happens at admission: after the device prefix cache
    (PageAllocator.acquire_cached) is consulted, the engine probes the
    tiers for the NEXT hashes in the chain; hits are scatter-injected
    (`inject_pages`) into freshly allocated device pages before prefill,
    extending the cached prefix and skipping that prefill compute. Under
    DYN_SCHED_POLICY=sla the engine first compares the tiers' observed
    per-block load latency against the slot's TTFT headroom and falls
    back to recompute when onboarding would blow the deadline.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, List, Optional, Sequence, Tuple

import numpy as np

from ..runtime import faults
from .storage import EVICTION_POLICIES, DiskTier, HostTier

logger = logging.getLogger(__name__)


def _parse_eviction(spec: Optional[str]) -> Tuple[str, str]:
    """DYN_KVBM_EVICTION: a single policy (`lru`) applies to both tiers;
    `host=lfu,disk=lru` sets them independently. Unknown spellings fall
    back to lru (an eviction-policy typo must not take the worker down)."""
    import os

    spec = spec if spec is not None else os.environ.get("DYN_KVBM_EVICTION")
    if not spec:
        return "lru", "lru"
    spec = spec.strip().lower()
    if "=" not in spec:
        if spec not in EVICTION_POLICIES:
            logger.warning("DYN_KVBM_EVICTION=%r unknown; using lru", spec)
            spec = "lru"
        return spec, spec
    out = {"host": "lru", "disk": "lru"}
    for part in spec.split(","):
        tier, _, pol = part.partition("=")
        tier, pol = tier.strip(), pol.strip()
        if tier not in out or pol not in EVICTION_POLICIES:
            logger.warning("DYN_KVBM_EVICTION part %r unknown; ignoring", part)
            continue
        out[tier] = pol
    return out["host"], out["disk"]


@dataclass
class KvbmConfig:
    host_blocks: int = 0  # G2 capacity (0 disables the tier)
    disk_blocks: int = 0  # G3 capacity (0 disables the tier)
    disk_path: Optional[str] = None
    eviction: Optional[str] = None  # None -> DYN_KVBM_EVICTION -> lru


class KvBlockManager:
    """Owns the G2/G3 tiers and the offload/onboard policy."""

    def __init__(self, cfg: KvbmConfig, block_shape: tuple, dtype,
                 kv_format: str = "none"):
        self.cfg = cfg
        self.block_shape = tuple(block_shape)
        self.dtype = dtype
        # quantized-KV page format this manager's tiers hold (docs/kvbm.md
        # "Quantized KV format"): under int8/int4 a block is ONE PACKED
        # uint8 row per layer (q bytes + per-page-per-head scales,
        # ops/kv_quant.py host layout) — tier capacity at fixed bytes
        # grows 2x/4x, and the format travels in the peer-pull handshake
        # so mixed-precision fleets fail typed (KvFormatError)
        self.kv_format = str(kv_format)
        # K+V bytes per block: the data plane sizes its inline-vs-executor
        # serve decision off this
        self.block_nbytes = 2 * int(np.prod(block_shape)) * np.dtype(dtype).itemsize
        if cfg.disk_blocks > 0 and not cfg.disk_path:
            raise ValueError("kvbm_disk_blocks > 0 requires kvbm_disk_path")
        host_policy, disk_policy = _parse_eviction(cfg.eviction)
        self.host: Optional[HostTier] = (
            HostTier(cfg.host_blocks, block_shape, dtype, policy=host_policy)
            if cfg.host_blocks > 0
            else None
        )
        self.disk: Optional[DiskTier] = (
            DiskTier(cfg.disk_blocks, block_shape, dtype, cfg.disk_path,
                     policy=disk_policy)
            if cfg.disk_blocks > 0
            else None
        )
        self._lock = threading.Lock()  # store runs on the kvbm-tier thread
        self.offloaded_blocks = 0
        self.onboarded_blocks = 0
        self.disk_evictions = 0
        self.dropped_blocks = 0
        # hashes that fell off the tier chain entirely since the last
        # drain: the announcement mesh must retract them, or peers keep
        # stale owner entries and probe onto dead blocks (the bounded-tier
        # + worker-churn resurrection bug)
        self._evicted_pending: List[int] = []
        # per-tier per-block load latency EWMA (ms): feeds the onboard
        # budget (estimate_load_ms). None until first observed — a cold
        # tier never defers an onboard (same rule as the scheduler's
        # CostModel: never-observed = no constraint).
        self._load_ms: dict = {"host": None, "disk": None}

    # -- store path (kvbm-tier thread) ----------------------------------- #

    def store(self, seq_hash: int, k: np.ndarray, v: np.ndarray,
              parent: Optional[int] = None):
        """Insert one block at the top of the G2->G3 chain, cascading the
        host tier's eviction down to disk. `parent` = preceding chain hash
        when known (prefix-aware eviction protection)."""
        with self._lock:
            if self.host is not None:
                evicted = self.host.put(seq_hash, k, v, parent=parent)
                self.offloaded_blocks += 1
                if evicted is not None:
                    old_hash, old_k, old_v, old_parent = evicted
                    if self.disk is not None:
                        dropped = self.disk.put(
                            old_hash, old_k, old_v, parent=old_parent
                        )
                        if dropped is not None:
                            self.dropped_blocks += 1
                            self._evicted_pending.append(int(dropped))
                        self.disk_evictions += 1
                    else:
                        self.dropped_blocks += 1
                        self._evicted_pending.append(int(old_hash))
            elif self.disk is not None:
                dropped = self.disk.put(seq_hash, k, v, parent=parent)
                if dropped is not None:
                    self.dropped_blocks += 1
                    self._evicted_pending.append(int(dropped))
                self.offloaded_blocks += 1

    def drain_evicted(self) -> List[int]:
        """Hashes dropped from ALL tiers since the last drain (the
        announcement mesh retracts these as `evicted`).

        Re-checked against the CURRENT tier contents before handing out:
        a hash evicted and then RE-STORED between the drop and this drain
        (same-prefix traffic re-offloading, a peer promotion) is still
        held here — retracting it would tell peers to forget a live
        owner, and nothing re-announces until the block churns again."""
        with self._lock:
            pending, self._evicted_pending = self._evicted_pending, []
            out: List[int] = []
            seen = set()
            for h in pending:
                if h in seen:
                    continue
                seen.add(h)
                present = (
                    self.host is not None and self.host.has(h)
                ) or (self.disk is not None and self.disk.has(h))
                if not present:
                    out.append(h)
            return out

    def all_hashes(self) -> List[int]:
        """Every block hash held in any tier (the announcement-mesh
        sync-reply payload)."""
        with self._lock:
            out = set()
            if self.host is not None:
                out.update(self.host._by_hash)
            if self.disk is not None:
                out.update(self.disk._by_hash)
            return sorted(out)

    def has(self, seq_hash: int) -> bool:
        with self._lock:
            if self.host is not None and self.host.has(seq_hash):
                return True
            return self.disk is not None and self.disk.has(seq_hash)

    # -- lookup path (event loop thread) --------------------------------- #

    def match_prefix(self, hashes: Sequence[int]) -> List[int]:
        """Longest leading run of `hashes` present in any tier."""
        out: List[int] = []
        for h in hashes:
            if self.has(h):
                out.append(h)
            else:
                break
        return out

    def estimate_load_ms(self, hashes: Sequence[int]) -> Optional[float]:
        """Projected load_blocks latency for `hashes` from the per-tier
        EWMAs. None when any needed tier has never been observed (cold
        tiers never defer an onboard) or when a hash is not tiered here
        (remote pull cost is unknowable locally)."""
        with self._lock:
            total = 0.0
            for h in hashes:
                if self.host is not None and self.host.has(h):
                    ms = self._load_ms["host"]
                elif self.disk is not None and self.disk.has(h):
                    ms = self._load_ms["disk"]
                else:
                    return None
                if ms is None:
                    return None
                total += ms
            return total

    def load_blocks(
        self, hashes: Sequence[int]
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch blocks (host first, then disk, promoting disk hits to host)
        stacked on a leading axis: [n, *block_shape]."""
        ks, vs = [], []
        with self._lock:
            for h in hashes:
                t0 = time.perf_counter()
                got = self.host.get(h) if self.host is not None else None
                src = "host"
                if got is None and self.disk is not None:
                    got = self.disk.get(h)
                    src = "disk"
                    if got is not None and self.host is not None:
                        # promotion carries the chain link: without it a
                        # just-promoted chain loses its prefix-aware
                        # descendant protection in the host tier
                        evicted = self.host.put(
                            h, got[0], got[1],
                            parent=self.disk._parent.get(h),
                        )
                        if evicted is not None:
                            old_hash, old_k, old_v, old_parent = evicted
                            dropped = self.disk.put(
                                old_hash, old_k, old_v, parent=old_parent
                            )
                            if dropped is not None:
                                self.dropped_blocks += 1
                                self._evicted_pending.append(int(dropped))
                            self.disk_evictions += 1
                if got is None:
                    raise KeyError(f"KVBM block {h} vanished between probe and load")
                # copy: get() returns views into the tier pools, and a later
                # promotion in this same loop may evict+overwrite those slots
                ks.append(np.array(got[0]))
                vs.append(np.array(got[1]))
                # per-tier load-latency EWMA feeding estimate_load_ms
                ms = (time.perf_counter() - t0) * 1000.0
                prev = self._load_ms[src]
                self._load_ms[src] = (
                    ms if prev is None else 0.8 * prev + 0.2 * ms
                )
            self.onboarded_blocks += len(hashes)
        return np.stack(ks), np.stack(vs)

    def read_blocks(
        self, hashes: Sequence[int]
    ) -> Tuple[List[int], np.ndarray, np.ndarray]:
        """Read-only fetch for the session-checkpoint replicator: no
        promotion, no hit/miss/onboard accounting, no recency touch — a
        background copy must not distort the tier stats or eviction order
        the serving path depends on. Missing hashes are silently skipped
        (evicted between stage and push: the checkpoint just loses that
        block, same drop-not-stall discipline as the offload queue).
        Returns (present_hashes, k [n,...], v [n,...])."""
        present: List[int] = []
        ks, vs = [], []
        with self._lock:
            for h in hashes:
                for tier in (self.host, self.disk):
                    if tier is None:
                        continue
                    slot = tier._by_hash.get(h)
                    if slot is not None:
                        present.append(int(h))
                        # copy: the views die with the next eviction
                        ks.append(np.array(tier._k[slot]))
                        vs.append(np.array(tier._v[slot]))
                        break
        if not present:
            return [], np.empty((0,)), np.empty((0,))
        return present, np.stack(ks), np.stack(vs)

    def flush(self):
        """Persist the disk tier's index (engine close / checkpoint)."""
        with self._lock:
            if self.disk is not None:
                self.disk.flush()

    def clear(self) -> int:
        """Drop every tiered block (admin clear-kv-blocks route)."""
        with self._lock:
            n = 0
            if self.host is not None:
                n += self.host.clear()
            if self.disk is not None:
                n += self.disk.clear()
                self.disk.flush()  # persist the now-empty index
            return n

    def stats(self) -> dict:
        # the event loop reads while the tier thread stores: the lock buys
        # a consistent counter+tier snapshot (GUARDED_STATE)
        with self._lock:
            out = {
                "kvbm_offloaded_blocks": self.offloaded_blocks,
                "kvbm_onboarded_blocks": self.onboarded_blocks,
                "kvbm_disk_evictions": self.disk_evictions,
                "kvbm_dropped_blocks": self.dropped_blocks,
            }
            if self.host is not None:
                out.update({f"kvbm_{k}": v for k, v in self.host.stats().items()})
                out["kvbm_host_eviction_policy"] = self.host.policy
            if self.disk is not None:
                out.update({f"kvbm_{k}": v for k, v in self.disk.stats().items()})
                out["kvbm_disk_eviction_policy"] = self.disk.policy
            for tier, ms in self._load_ms.items():
                if ms is not None:
                    out[f"kvbm_{tier}_load_ms_per_block"] = round(ms, 3)
            return out


@dataclass
class _OffloadBatch:
    """One step's coalesced commits, gathered on-device, awaiting the tier
    thread. `k`/`v` are jax device arrays ([layers, n, page, heads, dim]);
    np.asarray on the tier thread performs the device->host copy."""

    hashes: List[int]
    parents: List[Optional[int]]
    k: object = None
    v: object = None
    ready: bool = False  # gather dispatched (k/v populated)
    dropped: bool = False  # backpressure victim: tier thread must skip it
    # "offload" = this worker's own session commits (checkpoint-staged);
    # "promotion" = peer-pulled blocks entering the host tier (already
    # durable on the peer — replicating them would waste the data plane
    # AND crowd this worker's own sessions out of the bounded stage)
    origin: str = "offload"


class KvbmConnector:
    """Engine-side glue (reference block_manager/connector/scheduler.rs:
    the piece that integrates the pool with the engine's forward pass).

    Holds a reference to the JaxEngine for its jitted extract/inject ops
    and its serial device executor; see module docstring for the pipeline
    stages and the ordering argument that makes write-through offload
    race-free.
    """

    def __init__(self, engine, manager: KvBlockManager):
        from ..runtime.config import env_bool

        self.engine = engine
        self.manager = manager
        # cluster KV fabric (docs/kvbm.md): admission may onboard blocks
        # from a PEER worker's tiers over the data plane. Off = local
        # tiers only (the pre-fabric behavior).
        self.peer_pull = env_bool("DYN_KVBM_PEER_PULL", True)
        import os

        try:
            self.queue_cap = max(
                int(os.environ.get("DYN_KVBM_OFFLOAD_QUEUE") or 8), 1
            )
        except ValueError:
            self.queue_cap = 8
        # pipeline state — ALL of it guarded by _offload_cv's lock: the
        # event loop stages and flushes, the device-exec thread marks
        # batches ready, the kvbm-tier thread consumes (GUARDED_STATE)
        self._offload_cv = threading.Condition()
        self._staged: List[Tuple[int, int, Optional[int]]] = []  # (hash, phys_page, parent)
        self._queue: Deque[_OffloadBatch] = deque()
        self._inflight_hashes: set = set()  # staged or queued, pre-store
        self._processing = 0  # blocks of the batch the tier thread holds
        self._tier_thread: Optional[threading.Thread] = None
        self._stopped = False
        # counters (read via stats() under the cv lock)
        self.offload_commit_calls = 0
        self.offload_gathers = 0
        self.offload_batches_dropped = 0
        self.offload_blocks_dropped = 0
        self.offload_failures = 0
        self.onboard_recompute_fallbacks = 0
        # per-source onboard decision accounting (cluster KV fabric): how
        # many admission blocks came from the local tiers, from a peer
        # pull, and how many the budget handed back to recompute
        self.onboard_src_local_blocks = 0
        self.onboard_src_peer_blocks = 0
        self.onboard_src_recompute_blocks = 0
        # kvbm/distributed.py attaches itself here: cross-worker probe/pull
        # (the G4 role — peer memory as the tier below disk)
        self.distributed = None

    # -- offload (event loop: stage at commit, flush once per step) ------ #

    def offload_commit(self, seq_hashes: List[int], phys_pages: List[int],
                       parent: Optional[int] = None):
        """Write-through: snapshot the just-committed device pages into G2.
        Stage the pairs; the engine's end-of-step `flush_step()` coalesces
        every stage from this step into one gather. `parent` = hash chained
        immediately before `seq_hashes[0]` (None at a chain head)."""
        # probe the tiers BEFORE taking the cv: manager._lock nests under
        # _offload_cv nowhere (one global lock order, race-lock-order)
        missing = {h for h in seq_hashes if not self.manager.has(h)}
        with self._offload_cv:
            self.offload_commit_calls += 1
            prev = parent
            for h, p in zip(seq_hashes, phys_pages):
                if h in missing and h not in self._inflight_hashes:
                    self._staged.append((h, p, prev))
                    self._inflight_hashes.add(h)
                prev = h

    def flush_step(self):
        """Submit ONE gather for everything staged this step (engine step
        loop, once per `_step_once`). The gather job runs on the device
        executor but only dispatches; the device->host copy and tier
        stores happen on the kvbm-tier thread."""
        with self._offload_cv:
            if self._stopped or not self._staged:
                return
            staged, self._staged = self._staged, []
            batch = _OffloadBatch(
                hashes=[h for h, _, _ in staged],
                parents=[par for _, _, par in staged],
            )
            # backpressure: bound the not-yet-stored batches; the OLDEST
            # uncommitted batch is the least valuable (most likely already
            # superseded or about to be re-requested) — drop it, count it
            while len(self._queue) >= self.queue_cap:
                victim = self._queue.popleft()
                victim.dropped = True
                self.offload_batches_dropped += 1
                self.offload_blocks_dropped += len(victim.hashes)
                self._inflight_hashes.difference_update(victim.hashes)
            self._queue.append(batch)
            self.offload_gathers += 1
            self._ensure_tier_thread()
        # pad the gather to a pow2 page-count bucket (pad rows read the
        # scratch page and are never stored): a varying batch size would
        # compile a fresh extract_pages variant per distinct size —
        # unbounded compile space; buckets bound it at log2(max_batch)
        n = len(staged)
        bucket = 1 << (n - 1).bit_length()
        pages = np.zeros((bucket,), np.int32)
        pages[:n] = [p for _, p, _ in staged]
        eng = self.engine

        def run_gather():
            import jax.numpy as jnp

            try:
                k, v = eng._extract_pages(eng.kv_k, eng.kv_v, jnp.asarray(pages))
            except Exception as e:  # noqa: BLE001 — a failed gather loses
                # cache copies, never correctness; drop the batch
                logger.warning("KVBM offload gather failed: %s", e)
                with self._offload_cv:
                    if not batch.dropped:
                        # lost cache copies are DROPPED blocks wherever
                        # they die — dashboards alarm on one counter. A
                        # backpressure victim was already counted when it
                        # left the queue; its failing gather adds nothing.
                        self.offload_failures += 1
                        self.offload_blocks_dropped += len(batch.hashes)
                        self._inflight_hashes.difference_update(batch.hashes)
                    batch.dropped = True
                    batch.ready = True
                    self._offload_cv.notify_all()
                return
            with self._offload_cv:
                batch.k, batch.v = k, v
                batch.ready = True
                self._offload_cv.notify_all()

        # the device executor orders this gather before any later rewrite
        # of the same pages; _timed accrues its (dispatch-only) cost to
        # dispatch_kvbm_offload_* (stats(), the worker's metrics topic)
        eng._device_exec.submit(eng._timed(run_gather, "kvbm_offload"))

    def stage_promotion(self, hashes: Sequence[int],
                        parents: Sequence[Optional[int]], k, v):
        """Promote peer-pulled blocks into the host tier OFF the onboard
        critical path: enqueue a READY batch for the kvbm-tier thread
        (same bounded queue + drop-oldest backpressure as offload
        write-through). Losing a promotion under pressure loses a future
        local hit, never correctness — the peer still owns the block."""
        # _store_batch expects [layers, n, ...] like a device gather
        # (peer pulls arrive per-block [n, layers, ...] — fp typed rows or
        # quantized packed uint8 rows, either way a plain swapaxes)
        batch = _OffloadBatch(
            hashes=[int(h) for h in hashes],
            parents=list(parents),
            k=np.asarray(k).swapaxes(0, 1),
            v=np.asarray(v).swapaxes(0, 1),
            ready=True,
            origin="promotion",
        )
        with self._offload_cv:
            if self._stopped:
                return
            while len(self._queue) >= self.queue_cap:
                victim = self._queue.popleft()
                victim.dropped = True
                self.offload_batches_dropped += 1
                self.offload_blocks_dropped += len(victim.hashes)
                self._inflight_hashes.difference_update(victim.hashes)
            self._queue.append(batch)
            self._inflight_hashes.update(batch.hashes)
            self._ensure_tier_thread()
            self._offload_cv.notify_all()

    def _ensure_tier_thread(self):
        """Caller holds _offload_cv."""
        if self._tier_thread is None or not self._tier_thread.is_alive():
            self._tier_thread = threading.Thread(
                target=self._tier_loop, name="kvbm-tier", daemon=True
            )
            self._tier_thread.start()

    def _tier_loop(self):
        """Dedicated tier thread: device->host copy, G2 store, G2->G3
        cascade and G3 file I/O — everything the seed ran on the device
        executor past the gather. One batch at a time, FIFO."""
        while True:
            with self._offload_cv:
                while not self._stopped and not (
                    self._queue and self._queue[0].ready
                ):
                    self._offload_cv.wait()
                if self._stopped and not self._queue:
                    return
                batch = self._queue[0]
                if not batch.ready:
                    # stopped with an un-gathered batch queued: nothing to
                    # store — the device job will never mark it ready.
                    # These are lost cache copies like any other drop.
                    self._queue.popleft()
                    self.offload_batches_dropped += 1
                    self.offload_blocks_dropped += len(batch.hashes)
                    self._inflight_hashes.difference_update(batch.hashes)
                    continue
                self._queue.popleft()
                self._processing = len(batch.hashes)
            try:
                if batch.dropped:
                    continue
                try:
                    self._store_batch(batch)
                except faults.FaultError as e:
                    # dynochaos kvbm.offload `error`: the batch is dropped,
                    # counted, and the stream never notices — offload is a
                    # cache write, not part of any request's critical path
                    logger.warning("KVBM offload batch dropped (%s)", e)
                    with self._offload_cv:
                        self.offload_failures += 1
                        self.offload_blocks_dropped += len(batch.hashes)
                        self._inflight_hashes.difference_update(batch.hashes)
                except Exception:  # noqa: BLE001 — the tier thread must not die
                    logger.exception("KVBM offload store failed; batch dropped")
                    with self._offload_cv:
                        self.offload_failures += 1
                        self.offload_blocks_dropped += len(batch.hashes)
                        self._inflight_hashes.difference_update(batch.hashes)
            finally:
                with self._offload_cv:
                    self._processing = 0

    def _store_batch(self, batch: _OffloadBatch):
        f = faults.FAULTS
        if f.enabled:
            act = f.check("kvbm.offload")
            if act == "error":
                raise faults.FaultError("injected fault at kvbm.offload")
            if act == "delay":
                time.sleep(0.05)
        # host_pack_pages blocks until the async gather lands — on THIS
        # thread, not the device executor. fp: the seed's np.asarray;
        # quantized: packed uint8 [L, n, PB] rows (q bytes + scales).
        # [layers, n, ...] -> per-block [n, ...]
        from ..ops.kv_quant import host_pack_pages

        k_np = host_pack_pages(batch.k).swapaxes(0, 1)
        v_np = host_pack_pages(batch.v).swapaxes(0, 1)
        for i, h in enumerate(batch.hashes):
            self.manager.store(h, k_np[i], v_np[i], parent=batch.parents[i])
        with self._offload_cv:
            self._inflight_hashes.difference_update(batch.hashes)
        if self.distributed is not None:
            self.distributed.announce_threadsafe("stored", batch.hashes)
            self._announce_evictions()
            # session checkpointing (docs/fault_tolerance.md): every block
            # this worker COMMITS is also staged for replication to a
            # peer's G2 — bounded (newest refused), never blocks this
            # thread. Promotion batches (peer-pulled blocks) are not
            # staged: they are already durable on the peer that served
            # them, and re-pushing them would crowd this worker's own
            # live sessions out of the bounded stage
            ck = self.distributed.checkpointer
            if ck is not None and batch.origin == "offload":
                ck.stage_threadsafe(batch.hashes, batch.parents)

    def _announce_evictions(self):
        """Retract fully-dropped hashes from the mesh (any thread)."""
        if self.distributed is None:
            return
        evicted = self.manager.drain_evicted()
        if evicted:
            self.distributed.announce_threadsafe("evicted", evicted)

    # -- onboard (called at admission) ----------------------------------- #

    def probe(self, hashes: Sequence[int], hint_instance: Optional[int] = None,
              hint_blocks: int = 0) -> List[int]:
        """Longest onboardable prefix: local tiers, extended by remote
        owners when the distributed mesh is attached (G4 role). The
        router-supplied holder hint (`hint_instance` holds the first
        `hint_blocks` entries of THIS slice per the router's radix index)
        extends coverage past what the announcement mesh has mirrored."""
        local = self.manager.match_prefix(hashes)
        if (
            self.peer_pull and self.distributed is not None
            and len(local) < len(hashes)
        ):
            return list(local) + self.distributed.extend_prefix(
                list(hashes)[len(local):],
                hint_instance=hint_instance,
                hint_blocks=max(hint_blocks - len(local), 0),
            )
        return local

    def estimate_onboard_ms(self, hashes: Sequence[int]) -> Optional[float]:
        """Projected tier-load latency for an onboard of `hashes` (None =
        unknown; the engine only defers to recompute on a KNOWN blowout)."""
        return self.manager.estimate_load_ms(hashes)

    def budget_onboard(
        self,
        hashes: List[int],
        headroom_ms: Optional[float],
        recompute_ms_per_block: Optional[float],
        hint_instance: Optional[int] = None,
    ) -> Tuple[List[int], str]:
        """Three-arm onboard budget (docs/kvbm.md cluster KV fabric): the
        cheapest source wins per span — local-tier load vs per-peer
        transfer rate vs recompute — and a cold/slow peer never blocks
        TTFT past the slot's headroom.

        Returns (hashes_to_onboard, decision) with decision one of
        `full` (onboard everything probed), `trim-local` (keep the
        locally-tiered prefix, recompute the peer tail), `recompute`
        (skip the onboard entirely). Unknown costs never constrain: a
        cold tier/peer/cost-model keeps the full onboard, the same rule
        as the scheduler's CostModel."""
        if not hashes:
            return hashes, "full"
        local_mask = [self.manager.has(h) for h in hashes]
        n_total = len(hashes)
        # cost of the full onboard: local part at tier EWMA + peer part at
        # per-peer transfer EWMA; any unknown component -> unconstrained
        local_part = [h for h, m in zip(hashes, local_mask) if m]
        peer_part = [h for h, m in zip(hashes, local_mask) if not m]
        est_local = (
            self.manager.estimate_load_ms(local_part) if local_part else 0.0
        )
        if peer_part and (self.distributed is None or not self.peer_pull):
            # probe() can't have included peer blocks in that case, but a
            # racing eviction may have demoted a local hash: recompute it
            est_peer = None
        elif peer_part:
            est_peer = self.distributed.estimate_pull_ms(
                peer_part, hint_instance=hint_instance
            )
        else:
            est_peer = 0.0
        est_full = (
            est_local + est_peer
            if est_local is not None and est_peer is not None else None
        )
        if headroom_ms is None or est_full is None or est_full <= headroom_ms:
            self._count_onboard(len(local_part), len(peer_part), 0)
            return hashes, "full"
        if recompute_ms_per_block is None:
            # blown headroom but no recompute observation yet: we cannot
            # prove any alternative cheaper — keep the onboard
            self._count_onboard(len(local_part), len(peer_part), 0)
            return hashes, "full"
        # arm B: keep the locally-tiered PREFIX, recompute the rest (the
        # slow peer tail is the usual blowout); arm C: full recompute
        n_local_prefix = 0
        for m in local_mask:
            if not m:
                break
            n_local_prefix += 1
        est_prefix = (
            self.manager.estimate_load_ms(hashes[:n_local_prefix])
            if n_local_prefix else 0.0
        )
        cost_b = (
            est_prefix + recompute_ms_per_block * (n_total - n_local_prefix)
            if est_prefix is not None else None
        )
        cost_c = recompute_ms_per_block * n_total
        best, decision = est_full, "full"
        if cost_c < best:
            best, decision = cost_c, "recompute"
        if cost_b is not None and n_local_prefix and cost_b < best:
            best, decision = cost_b, "trim-local"
        if decision == "full":
            self._count_onboard(len(local_part), len(peer_part), 0)
            return hashes, "full"
        if decision == "trim-local":
            kept = hashes[:n_local_prefix]
            self._count_onboard(len(kept), 0, n_total - len(kept))
            self.note_onboard_recompute()
            return kept, "trim-local"
        self._count_onboard(0, 0, n_total)
        self.note_onboard_recompute()
        return [], "recompute"

    def _count_onboard(self, n_local: int, n_peer: int, n_recompute: int):
        with self._offload_cv:
            self.onboard_src_local_blocks += n_local
            self.onboard_src_peer_blocks += n_peer
            self.onboard_src_recompute_blocks += n_recompute

    def note_onboard_recompute(self):
        """The engine skipped (part of) an onboard whose projected load
        latency exceeded the slot's TTFT headroom and lost to recompute
        (docs/kvbm.md onboard budget)."""
        with self._offload_cv:
            self.onboard_recompute_fallbacks += 1

    def any_checkpoint(self, hashes: Sequence[int]) -> bool:
        """True when any of `hashes` is a session-checkpoint replica —
        pushed INTO this worker's tiers by a peer's checkpointer, or
        mesh-announced as checkpointed elsewhere. Drives the engine's
        resume-source classification for migrated requests."""
        return (
            self.distributed is not None
            and self.distributed.any_checkpoint(hashes)
        )

    def load(self, hashes: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
        return self.manager.load_blocks(hashes)

    async def load_async(self, hashes: Sequence[int], run,
                         hint_instance: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Onboard path: local tier reads ride the engine's device/IO
        executor (`run`), remote blocks pull point-to-point from their
        owner's data plane (announced owner, falling back to the router's
        holder hint) and are PROMOTED into the local host tier so repeat
        hits stay local. Raises KeyError on any miss (the engine falls
        back to prefilling that span); a dynochaos `kvbm.onboard` error or
        a typed KvTransferError (severed/unreachable peer) rides the same
        fallback."""
        f = faults.FAULTS
        if f.enabled:
            # FaultError propagates to _inject_onboard, which treats it
            # exactly like an evicted block: recompute that span
            await f.on("kvbm.onboard")
        local = [h for h in hashes if self.manager.has(h)]
        remote = [h for h in hashes if not self.manager.has(h)]
        # `hashes` is a contiguous onboard span: each hash's predecessor
        # is its chain parent (first unknown) — promotion keeps the links
        parent_of: dict = {}
        prev = None
        for h in hashes:
            parent_of[h] = prev
            prev = h
        parts: dict = {}
        if remote:
            if self.distributed is None or not self.peer_pull:
                raise KeyError(f"kvbm blocks {remote[:3]}... not tiered here")
            try:
                rk, rv = await self.distributed.pull_blocks(
                    remote, hint_instance=hint_instance
                )
            except KeyError:
                raise
            except Exception as e:  # noqa: BLE001 — dead peer / severed
                from ..llm.kv_transfer import KvFormatError

                if isinstance(e, KvFormatError):
                    # mixed-precision fleet: stays TYPED all the way up —
                    # the engine counts it (kv_format_mismatches) before
                    # falling back to recompute
                    raise
                # stream / unresolvable addr (KvTransferError) or any other
                # transport failure: the engine treats a KeyError as
                # "prefill that span instead"
                raise KeyError(f"kvbm remote pull failed: {e}") from e

            # promotion rides the tier thread, not the onboard critical
            # path (stage_promotion) — the slot's inject proceeds
            # immediately
            self.stage_promotion(
                remote, [parent_of[h] for h in remote], rk, rv
            )
            if not local:
                # pull_blocks stacked in `hashes` order already — skip
                # the per-block restack copy (admission latency path)
                return rk, rv
            for i, h in enumerate(remote):
                parts[h] = (rk[i], rv[i])
        if local:
            if not remote:
                out = await run(self.manager.load_blocks, local)
                # disk→host promotion inside load_blocks can cascade
                # drops: retract them even on a read-only path (a worker
                # that mostly SERVES pulls would otherwise never drain)
                self._announce_evictions()
                return out
            lk, lv = await run(self.manager.load_blocks, local)
            self._announce_evictions()
            for i, h in enumerate(local):
                parts[h] = (lk[i], lv[i])
        ks = np.stack([parts[h][0] for h in hashes])
        vs = np.stack([parts[h][1] for h in hashes])
        return ks, vs

    def clear(self) -> int:
        n = self.manager.clear()
        if self.distributed is not None:
            self.distributed.announce("cleared", [])
        return n

    def pending_offloads(self) -> int:
        """In-flight write-through count: staged pairs + queued batches'
        blocks + the batch mid-store on the tier thread (engine close()
        drains on this)."""
        with self._offload_cv:
            return (
                len(self._staged)
                + sum(len(b.hashes) for b in self._queue)
                + self._processing
            )

    def drain(self, timeout_s: float = 5.0) -> bool:
        """Block (event-loop-free callers only) until every staged/queued
        offload is stored or dropped. Returns False on timeout."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.pending_offloads() == 0:
                return True
            time.sleep(0.005)
        return self.pending_offloads() == 0

    def shutdown(self):
        """Stop the tier thread after the queue empties (engine close();
        call after an async drain)."""
        with self._offload_cv:
            self._stopped = True
            self._offload_cv.notify_all()
        t = self._tier_thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)

    def stats(self) -> dict:
        with self._offload_cv:
            queue_depth = len(self._queue)
            staged = len(self._staged)
            out = {
                "kvbm_offload_commit_calls": self.offload_commit_calls,
                "kvbm_offload_gathers": self.offload_gathers,
                "kvbm_offload_queue_depth": queue_depth,
                "kvbm_offload_staged_blocks": staged,
                "kvbm_offload_batches_dropped": self.offload_batches_dropped,
                "kvbm_offload_blocks_dropped": self.offload_blocks_dropped,
                "kvbm_offload_failures": self.offload_failures,
                "kvbm_onboard_recompute_fallbacks": self.onboard_recompute_fallbacks,
                "kvbm_onboard_src_local_blocks": self.onboard_src_local_blocks,
                "kvbm_onboard_src_peer_blocks": self.onboard_src_peer_blocks,
                "kvbm_onboard_src_recompute_blocks": self.onboard_src_recompute_blocks,
            }
        out.update(self.manager.stats())
        out["kvbm_pending_offloads"] = self.pending_offloads()
        if self.distributed is not None:
            out.update(self.distributed.stats())
        return out
