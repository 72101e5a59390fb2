"""KV-cache data plane: the NIXL replacement's fast path.

The reference moves prefill→decode KV via NIXL RDMA: the prefill side
registers memory and advertises descriptors, the decode side pulls with
`begin_read` while its engine keeps stepping
(lib/bindings/python/src/dynamo/nixl_connect/__init__.py:501-723,
lib/llm/src/block_manager/storage/nixl.rs). The TPU-native equivalent here
keeps the same *shape* — descriptor rendezvous + receiver-driven pull +
transfer/compute overlap — with transports that fit TPU hosts:

  * **staged pull over a dedicated TCP data plane**: the prefill worker
    runs a `KvDataPlaneServer` on its own port (NOT the request plane — a
    streaming KV payload must never head-of-line-block token traffic).
    Finishing a remote prefill *stages* the slot's pages and returns only a
    small descriptor on the response stream; the decode worker connects and
    pulls page CHUNKS, injecting each into its own cache while later chunks
    are still in flight. Frames carry raw page bytes (length-prefixed, no
    msgpack of the bulk) written straight from the array's memoryview.
  * **in-process device path**: when both engines share a process (one
    host serving both roles, or tests), the descriptor resolves through a
    process-local registry and chunks hand over as device arrays —
    extract→inject without host serialization. A multi-slice deployment
    whose prefill+decode meshes share one jax.distributed world can swap
    this transport for ppermute/DCN collectives behind the same interface.

Descriptors are also advertised under `v1/kv_data_plane/<instance>` in
discovery (the NIXL-metadata-in-etcd rendezvous, docs/architecture/
dynamo_flow.md S8/S10), so any worker can locate a peer's data plane
without a request-plane hop.
"""

from __future__ import annotations

import asyncio
import logging
import secrets
import struct
import time
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Dict, Optional, Sequence, Tuple

import msgpack
import numpy as np

from ..runtime import faults

logger = logging.getLogger(__name__)


class KvTransferError(RuntimeError):
    """A KV data-plane transfer failed (peer unreachable, addr no longer
    resolving, severed stream, protocol violation). Typed so the onboard /
    disagg paths can convert it to a clean recompute/local-prefill fallback
    instead of letting a raw ConnectionError escape into the step loop."""


class KvFormatError(KvTransferError):
    """The two ends of a KV transfer run different page formats
    (DYN_KV_QUANT mixed-precision fleet, docs/kvbm.md mixed-fleet rules).
    Raised BEFORE any payload bytes are interpreted: a format mismatch
    must fail typed — countable, alertable — never silently reinterpret
    quantized bytes as fp pages (or vice versa)."""


_MAGIC = 0xD7A04B1D  # frame magic (full-stream pull handshake)
_MAGIC_RANGE = 0xD7A04B1E  # ranged pull handshake (multi-host shard chunks)
_HDR = struct.Struct("<II")  # magic, header length

DATA_PLANE_ROOT = "v1/kv_data_plane/"

# hard server-side cap on one checkpoint push's block payload; the
# checkpointer sizes its batches to half this (bytes, not block count —
# a large-KV config would otherwise build full batches no server accepts)
CHECKPOINT_MAX_PAYLOAD = 512 << 20

# process-local rendezvous: (addr, transfer_id) -> _Staged. The in-process
# device-direct path (co-located prefill/decode engines) resolves here and
# never touches the socket.
_LOCAL: Dict[Tuple[str, str], "_Staged"] = {}


def _np_bytes(a: np.ndarray) -> memoryview:
    """Zero-copy view of an array's bytes (contiguous arrays only)."""
    a = np.ascontiguousarray(a)
    return a.reshape(-1).view(np.uint8).data


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def _set_nodelay(writer: asyncio.StreamWriter):
    """Disable Nagle on a KV data-plane socket: header+payload frames are
    written back-to-back and a coalescing delay on either end stalls the
    pull round-trip (admission-latency path)."""
    import socket

    try:
        sock = writer.get_extra_info("socket")
        if sock is not None:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass


def routable_host() -> str:
    """Best-effort routable address for descriptor advertisement. Binding to
    0.0.0.0 and advertising 127.0.0.1 silently defeats cross-host disagg
    (every pull connects to self and falls back to local prefill), so default
    to the interface a remote peer would reach us on."""
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        # no packets are sent; this just asks the kernel for the route
        s.connect(("10.255.255.255", 1))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


@dataclass
class KvTransferDescriptor:
    """What rides the response stream instead of the KV payload (the NIXL
    descriptor role)."""

    transfer_id: str
    addr: str  # host:port of the staging worker's data plane
    n_pages: int
    n_tokens: int
    page_size: int
    page_shape: list  # per-page block shape [L, page, KH, D]; a chunk of n
    # pages is layer-major [L, n, page, KH, D] (the engine's KV layout)
    dtype: str
    chunk_pages: int
    # multi-host shard rendezvous: host h of the PULLING worker fetches its
    # own shard's chunks (ranged pulls) from shards[h]["addr"] under the
    # shared transfer_id. page_shape is then the SHARD's per-page shape
    # (KH split across hosts). None => single staging endpoint (full pages).
    shards: Optional[list] = None  # [{"host_id": int, "addr": str}]
    # streamed staging: the producer is still prefilling when this
    # descriptor ships — chunks become pullable as pages commit, so the
    # puller must tolerate producer-paced gaps between chunks
    streamed: bool = False
    # quantized-KV page format ("none" | "int8" | "int4"): under quant,
    # page_shape is the PACKED host layout [L, PAGE_BYTES] uint8 (q bytes
    # + per-page-per-head scales, ops/kv_quant.py) and the puller must
    # run the same format — checked typed (KvFormatError) before pulling
    kv_format: str = "none"

    def to_dict(self) -> dict:
        return dict(self.__dict__)

    @classmethod
    def from_dict(cls, d: dict) -> "KvTransferDescriptor":
        import dataclasses as _dc

        known = {f.name for f in _dc.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


# extract(page_offset, n_pages, device) -> (k, v) with leading dim n_pages;
# may return jax arrays when device=True (in-process path)
ExtractFn = Callable[[int, int, bool], Awaitable[Tuple[Any, Any]]]


@dataclass
class _Staged:
    desc: KvTransferDescriptor
    extract: ExtractFn
    on_done: Callable[[bool], None]  # called exactly once; arg = pulled ok
    deadline: float
    max_transfer_time: float = 120.0  # per-chunk deadline extension budget
    started: bool = False
    finished: bool = False
    server: Optional["KvDataPlaneServer"] = None  # for serve accounting
    # streamed staging (disagg early handoff, docs/disagg_serving.md): the
    # transfer is staged while the producing prefill is STILL RUNNING.
    # `available` = pages valid so far (None = all pages, the non-streamed
    # default); the producer advances it as prefill chunks commit and the
    # serve loop waits on `avail_event` before extracting past it. `failed`
    # aborts waiting pullers (producer died / preempted mid-stream).
    available: Optional[int] = None
    failed: bool = False
    avail_event: Optional[asyncio.Event] = None

    def set_available(self, n_pages: int):
        if self.available is not None and n_pages > self.available:
            self.available = min(n_pages, self.desc.n_pages)
            # a progressing producer keeps the transfer alive
            self.deadline = time.monotonic() + self.max_transfer_time
            if self.avail_event is not None:
                self.avail_event.set()

    def fail_stream(self):
        self.failed = True
        if self.avail_event is not None:
            self.avail_event.set()

    async def wait_pages(self, upto: int):
        """Block until pages [0, upto) are valid (streamed staging); no-op
        for fully-staged transfers. Raises KvTransferError when the
        producer fails or the transfer is reaped mid-wait."""
        while True:
            if self.failed:
                raise KvTransferError("streamed kv transfer failed at source")
            if self.finished:
                raise KvTransferError("kv transfer reaped mid-stream")
            if self.available is None or self.available >= upto:
                return
            self.avail_event.clear()
            try:
                await asyncio.wait_for(
                    self.avail_event.wait(), self.max_transfer_time
                )
            except (TimeoutError, asyncio.TimeoutError) as e:
                raise KvTransferError(
                    "streamed kv transfer stalled (producer made no "
                    f"progress past page {self.available})"
                ) from e

    def count_serve(self, nbytes: int):
        """Account a served chunk (socket OR in-process) on the owning
        server's counters."""
        if self.server is not None:
            self.server.transfers_served += 1
            self.server.bytes_served += nbytes

    def finish(self, ok: bool):
        if not self.finished:
            self.finished = True
            try:
                self.on_done(ok)
            except Exception:  # noqa: BLE001 — release callbacks must not kill the server
                logger.exception("kv transfer on_done failed")


class KvDataPlaneServer:
    """Prefill-side staging server: holds pinned transfers, streams chunks
    to pulling peers, reaps abandoned transfers so their pages free."""

    def __init__(self, host: str = "0.0.0.0", advertise_host: Optional[str] = None,
                 port: int = 0, ttl: float = 30.0, max_transfer_time: float = 120.0,
                 chunk_timeout: float = 30.0):
        self._host = host
        self._advertise_host = advertise_host or (
            routable_host() if host in ("0.0.0.0", "") else host
        )
        self._port = port
        self.ttl = ttl
        # a pull that has *started* gets this long to finish before the
        # reaper unstages it (half-open peers must not pin pages forever)
        self.max_transfer_time = max_transfer_time
        self.chunk_timeout = chunk_timeout
        self._server: Optional[asyncio.AbstractServer] = None
        self._connections: set = set()
        self._staged: Dict[str, _Staged] = {}
        self._reaper: Optional[asyncio.Task] = None
        # observability: exact evidence that THIS host's data plane moved
        # bytes (the disagg tests assert on these — a silent local-prefill
        # fallback must not be able to masquerade as a working data plane)
        self.transfers_served = 0
        self.bytes_served = 0
        # distributed KVBM (kvbm/distributed.py): when set, `{"blocks": [...]}`
        # handshakes resolve straight from the tier manager — peers onboard
        # blocks this worker offloaded (reference KvbmLeader/Worker role)
        self.kvbm_source = None
        # back-pointer to KvbmDistributed: the checkpoint-receive path
        # tags stored replicas + announces them on the mesh
        self.kvbm_distributed = None
        # session-checkpoint pushes accepted into our tiers
        self.checkpoint_pushes = 0
        self.checkpoint_blocks_received = 0

    @property
    def addr(self) -> str:
        return f"{self._advertise_host}:{self._port}"

    async def start(self):
        self._server = await asyncio.start_server(self._serve, self._host, self._port)
        self._port = self._server.sockets[0].getsockname()[1]
        self._reaper = asyncio.create_task(self._reap_loop())

    async def close(self):
        if self._reaper is not None:
            self._reaper.cancel()
        for t in list(self._staged.values()):
            self._unstage(t, ok=False)
        if self._server is not None:
            self._server.close()
            # close live connections, else wait_closed() sits out every
            # peer's pooled keep-alive until its chunk_timeout
            for writer in list(self._connections):
                writer.close()
            await self._server.wait_closed()

    async def register(self, drt):
        """Advertise this data plane in discovery (NIXL-metadata rendezvous)."""
        import json

        try:
            await drt.put_leased(
                f"{DATA_PLANE_ROOT}{drt.instance_id:x}",
                json.dumps({"addr": self.addr}).encode(),
            )
        except Exception:  # noqa: BLE001 — advertisement is best-effort
            logger.warning("could not advertise kv data plane", exc_info=True)

    def stage(
        self,
        *,
        n_pages: int,
        n_tokens: int,
        page_size: int,
        page_shape: list,
        dtype: str,
        extract: ExtractFn,
        on_done: Callable[[bool], None],
        chunk_pages: int = 0,
        ttl: Optional[float] = None,
        transfer_id: Optional[str] = None,
        streamed: bool = False,
        available_pages: int = 0,
        kv_format: str = "none",
    ) -> KvTransferDescriptor:
        """Pin a finished prefill's pages for pulling; returns the descriptor
        to send on the response stream. `on_done(ok)` fires exactly once —
        on successful pull, pull failure, or TTL expiry — and is where the
        engine releases the slot's pages. An explicit `transfer_id` lets
        every host of a multi-host worker stage its shard under ONE id (the
        leader picks the id and broadcasts it in the stage_shard step
        descriptor). `streamed=True` stages a transfer whose producer is
        still running: only `available_pages` are valid yet, the producer
        advances the watermark via `advance_streamed` as pages commit, and
        pullers wait at the watermark instead of reading garbage."""
        if chunk_pages <= 0:
            # ~4 MiB/chunk of K (plus V): small enough to overlap, large
            # enough that framing cost vanishes
            per_page = int(np.prod(page_shape)) * _np_dtype(dtype).itemsize
            chunk_pages = max(1, (4 << 20) // max(per_page, 1))
        transfer_id = transfer_id or secrets.token_hex(8)
        desc = KvTransferDescriptor(
            transfer_id=transfer_id,
            addr=self.addr,
            n_pages=n_pages,
            n_tokens=n_tokens,
            page_size=page_size,
            page_shape=list(page_shape),
            dtype=dtype,
            chunk_pages=chunk_pages,
            streamed=streamed,
            kv_format=kv_format,
        )
        staged = _Staged(
            desc=desc,
            extract=extract,
            on_done=on_done,
            deadline=time.monotonic() + (ttl if ttl is not None else self.ttl),
            max_transfer_time=self.max_transfer_time,
            server=self,
            available=min(max(available_pages, 0), n_pages) if streamed else None,
            avail_event=asyncio.Event() if streamed else None,
        )
        self._staged[transfer_id] = staged
        _LOCAL[(self.addr, transfer_id)] = staged
        return desc

    def advance_streamed(self, transfer_id: str, available_pages: int):
        """Producer-side watermark: pages [0, available_pages) are now
        valid. No-op for unknown/non-streamed transfers."""
        staged = self._staged.get(transfer_id)
        if staged is not None:
            staged.set_available(available_pages)

    def abort_streamed(self, transfer_id: str):
        """Producer died (preempt / engine failure) mid-stream: wake and
        fail any waiting puller, release the stage."""
        staged = self._staged.get(transfer_id)
        if staged is not None:
            staged.fail_stream()
            self._unstage(staged, ok=False)

    def _unstage(self, staged: _Staged, ok: bool):
        self._staged.pop(staged.desc.transfer_id, None)
        _LOCAL.pop((self.addr, staged.desc.transfer_id), None)
        staged.finish(ok)

    def unstage_by_id(self, transfer_id: str, ok: bool) -> None:
        """Explicit release (multi-host shard staging: the leader decides
        when a transfer is over and broadcasts unstage_shard to followers —
        ranged pulls have no single is-done connection)."""
        staged = self._staged.get(transfer_id)
        if staged is not None:
            self._unstage(staged, ok)

    async def _reap_loop(self):
        while True:
            await asyncio.sleep(1.0)
            now = time.monotonic()
            for t in list(self._staged.values()):
                if t.finished:
                    # in-process pulls finish without passing through _serve;
                    # drop the bookkeeping entry so _staged stays bounded
                    self._staged.pop(t.desc.transfer_id, None)
                elif now > t.deadline:
                    logger.warning(
                        "kv transfer %s %s; releasing",
                        t.desc.transfer_id,
                        "stalled mid-pull" if t.started else "never pulled",
                    )
                    self._unstage(t, ok=False)

    async def _serve(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        _set_nodelay(writer)
        self._connections.add(writer)
        try:
            # ranged/kvbm requests are request-response and KEEP the
            # connection: a peer onboarding at admission rate would
            # otherwise pay a TCP connect per request (the client keeps a
            # small per-addr pool, _ConnPool). Idle connections die at the
            # chunk timeout; full-stream transfer pulls still close after
            # the one transfer.
            while True:
                try:
                    hdr = await asyncio.wait_for(
                        reader.readexactly(_HDR.size), self.chunk_timeout
                    )
                except (TimeoutError, asyncio.TimeoutError,
                        asyncio.IncompleteReadError):
                    return  # idle keep-alive or clean peer close
                magic, length = _HDR.unpack(hdr)
                if magic not in (_MAGIC, _MAGIC_RANGE):
                    raise RuntimeError(f"bad kv data plane magic {magic:#x}")
                # _MAGIC handshakes carry a 16-hex-char transfer id;
                # _MAGIC_RANGE handshakes may carry a {"blocks": [up to
                # 4096 x u64]} kvbm request (~9 B/hash => up to ~40 KiB)
                cap = 65536 if magic == _MAGIC_RANGE else 4096
                if length > cap:
                    raise RuntimeError(f"oversized kv handshake ({length} bytes)")
                body = await asyncio.wait_for(
                    reader.readexactly(length), self.chunk_timeout
                )
                if magic == _MAGIC_RANGE:
                    await self._serve_range(body, writer, reader)
                    continue
                await self._serve_transfer(body, writer)
                return
        except (asyncio.IncompleteReadError, ConnectionError):
            pass  # peer vanished; reaper/unstage already handled pages
        except Exception:  # noqa: BLE001 — one bad peer must not kill the server
            logger.exception("kv data plane connection failed")
        finally:
            self._connections.discard(writer)
            writer.close()

    async def _serve_transfer(self, body: bytes, writer: asyncio.StreamWriter):
        """Full-stream transfer pull (one per connection; _serve closes
        after). Errors propagate to _serve's handler."""
        transfer_id = body.decode()
        staged = self._staged.get(transfer_id)
        if staged is None or staged.started:
            await self._send_header(writer, {"error": f"unknown transfer {transfer_id}"})
            return
        staged.started = True
        staged.deadline = time.monotonic() + self.max_transfer_time
        try:
            await self._stream(staged, writer)
        except (ConnectionError, asyncio.IncompleteReadError,
                TimeoutError, asyncio.TimeoutError,
                KvTransferError):  # asyncio.TimeoutError
            # is distinct from builtin TimeoutError before 3.11;
            # KvTransferError = streamed producer failed/stalled
            self._unstage(staged, ok=False)
            raise
        self.transfers_served += 1
        self._unstage(staged, ok=True)

    async def _serve_range(self, body: bytes, writer: asyncio.StreamWriter,
                           reader: Optional[asyncio.StreamReader] = None):
        """One ranged request -> one (k, v) frame. Ranged pulls are how a
        multi-host decode worker's host h fetches chunk (off, n) of ITS
        shard from the matching prefill host: many connections may read the
        same staged transfer, so completion is signalled out-of-band
        (unstage_by_id from the leader's unstage_shard broadcast), with the
        TTL/deadline reaper as backstop."""
        req = msgpack.unpackb(body, raw=False)
        if req.get("ckpt") is not None and reader is not None:
            await self._serve_checkpoint(req["ckpt"], reader, writer)
            return
        if req.get("blocks") is not None:
            await self._serve_kvbm_blocks(req, writer)
            return
        transfer_id = req.get("tid", "")
        staged = self._staged.get(transfer_id)
        if staged is None:
            await self._send_header(writer, {"error": f"unknown transfer {transfer_id}"})
            return
        if req.get("fin"):
            # puller-side completion signal: release now instead of at TTL
            # (a control message — not counted as a served transfer)
            self._unstage(staged, ok=True)
            await self._send_header(writer, {"ok": True})
            return
        off, n = int(req.get("off", 0)), int(req.get("n", 0))
        if not (0 <= off and 0 < n and off + n <= staged.desc.n_pages):
            await self._send_header(writer, {"error": f"range out of bounds ({off},{n})"})
            return
        if staged.available is not None and off + n > staged.available:
            # ranged pulls (multi-host shards) don't ride streamed staging:
            # refuse reads past the producer's watermark instead of
            # serving uncommitted pages
            await self._send_header(
                writer, {"error": f"range past stream watermark ({off},{n})"}
            )
            return
        # a transfer being actively range-pulled is alive: refresh its clock
        staged.deadline = time.monotonic() + self.max_transfer_time
        np_dtype = _np_dtype(staged.desc.dtype)
        k, v = await staged.extract(off, n, False)
        k = np.asarray(k, np_dtype)
        v = np.asarray(v, np_dtype)
        kb, vb = _np_bytes(k), _np_bytes(v)
        await self._send_header(
            writer, {"off": off, "n": n, "k_bytes": len(kb), "v_bytes": len(vb)}
        )
        writer.write(kb)
        writer.write(vb)
        await asyncio.wait_for(writer.drain(), self.chunk_timeout)
        staged.count_serve(len(kb) + len(vb))

    async def _serve_kvbm_blocks(self, req: dict, writer: asyncio.StreamWriter):
        """Serve tiered KV blocks by hash (distributed KVBM onboard path,
        kvbm/distributed.py). One request -> one stacked (k, v) frame."""
        if self.kvbm_source is None:
            await self._send_header(writer, {"error": "no kvbm tier here"})
            return
        hashes = [int(h) for h in req["blocks"]]
        if not hashes or len(hashes) > 4096:
            await self._send_header(writer, {"error": f"bad block count {len(hashes)}"})
            return
        my_fmt = str(getattr(self.kvbm_source, "kv_format", "none"))
        want_fmt = str(req.get("fmt", "none"))
        if want_fmt != my_fmt:
            # mixed-precision fleet: refuse TYPED before any block bytes
            # move — the puller raises KvFormatError, never misreads rows
            await self._send_header(
                writer,
                {"error": f"kv_format mismatch: serving {my_fmt}, "
                          f"peer wants {want_fmt}",
                 "fmt_mismatch": True, "fmt": my_fmt},
            )
            return
        try:
            # tier reads do host memcpy/disk IO: off the event loop —
            # EXCEPT small host-tier-only reads, where the executor
            # round-trip costs more than the memcpy it protects against
            # (admission-rate peer pulls of a few small blocks)
            src = self.kvbm_source
            small = (
                getattr(src, "disk", None) is None
                and getattr(src, "block_nbytes", 1 << 30) * len(hashes)
                <= (256 << 10)
            )
            if small:
                k, v = src.load_blocks(hashes)
            else:
                k, v = await asyncio.get_running_loop().run_in_executor(
                    None, src.load_blocks, hashes
                )
        except KeyError as e:
            await self._send_header(writer, {"error": f"block miss: {e}"})
            return
        kb, vb = _np_bytes(k), _np_bytes(v)
        # header + payload in ONE buffered write/drain: the pull RTT is
        # admission latency on the peer, every syscall batch counts
        hdr_body = msgpack.packb(
            {"n": len(hashes), "k_bytes": len(kb), "v_bytes": len(vb),
             "shape": list(k.shape), "dtype": str(k.dtype), "fmt": my_fmt},
            use_bin_type=True,
        )
        writer.write(_HDR.pack(_MAGIC, len(hdr_body)) + hdr_body)
        writer.write(kb)
        writer.write(vb)
        await asyncio.wait_for(writer.drain(), self.chunk_timeout)
        self.transfers_served += 1
        self.bytes_served += len(kb) + len(vb)

    async def _drain_payload(self, reader: asyncio.StreamReader, n: int):
        """Read and discard `n` payload bytes after a refused push so the
        keep-alive connection stays framed for the next request."""
        while n > 0:
            chunk = await asyncio.wait_for(
                reader.read(min(n, 1 << 20)), self.chunk_timeout
            )
            if not chunk:
                raise asyncio.IncompleteReadError(b"", n)
            n -= len(chunk)

    async def _serve_checkpoint(self, meta: dict,
                                reader: asyncio.StreamReader,
                                writer: asyncio.StreamWriter):
        """Session-checkpoint PUSH (kvbm/checkpoint.py): a peer replicates
        committed session blocks into OUR host tier so a death on its side
        resumes from here. Header carries hashes/parents/format/sizes; the
        block bytes follow on the same connection. Refusals (no tiers,
        kv_format mismatch, bad sizes) drain the payload and answer typed
        BEFORE any byte is interpreted — mixed-precision fleets fail
        loudly, never store misread rows."""
        hashes = [int(h) for h in meta.get("blocks") or []]
        parents = [
            None if p is None else int(p)
            for p in (meta.get("parents") or [None] * len(hashes))
        ]
        k_bytes = int(meta.get("k_bytes") or 0)
        v_bytes = int(meta.get("v_bytes") or 0)
        payload = k_bytes + v_bytes
        if (
            not hashes or len(hashes) > 4096 or len(parents) != len(hashes)
            or payload <= 0
        ):
            raise RuntimeError(f"bad checkpoint push ({len(hashes)} blocks, "
                               f"{payload} bytes)")
        if payload > CHECKPOINT_MAX_PAYLOAD:
            # oversized but well-formed: the declared size is bounded
            # enough to drain, so answer typed on the kept connection —
            # tearing it here would cost the pusher a reconnect AND
            # misattribute a sizing bug as a dead peer (quarantine)
            if payload > 2 * CHECKPOINT_MAX_PAYLOAD:
                raise RuntimeError(
                    f"checkpoint push payload absurd ({payload} bytes)"
                )
            await self._drain_payload(reader, payload)
            await self._send_header(
                writer, {"error": f"checkpoint payload too large "
                                  f"({payload} > {CHECKPOINT_MAX_PAYLOAD})",
                         "peer_blameless": True}
            )
            return
        src = self.kvbm_source
        if src is None:
            await self._drain_payload(reader, payload)
            await self._send_header(
                writer, {"error": "no kvbm tier here", "ckpt_ineligible": True}
            )
            return
        my_fmt = str(getattr(src, "kv_format", "none"))
        want_fmt = str(meta.get("fmt", "none"))
        if want_fmt != my_fmt:
            await self._drain_payload(reader, payload)
            await self._send_header(
                writer,
                {"error": f"kv_format mismatch: holding {my_fmt}, "
                          f"peer pushes {want_fmt}",
                 "fmt_mismatch": True, "fmt": my_fmt},
            )
            return
        np_dtype = np.dtype(src.dtype)
        expect = int(np.prod(src.block_shape)) * np_dtype.itemsize * len(hashes)
        if k_bytes != expect or v_bytes != expect:
            # block geometry (dtype/page size/layers) is static for a
            # process's lifetime: same structural class as a kv_format
            # mismatch, so the pusher must exclude us durably — a TTL
            # quarantine would re-offer the same doomed bytes forever
            await self._drain_payload(reader, payload)
            await self._send_header(
                writer, {"error": f"checkpoint size mismatch "
                                  f"({k_bytes}+{v_bytes} != 2x{expect})",
                         "ckpt_ineligible": True}
            )
            return
        raw = await asyncio.wait_for(
            reader.readexactly(payload), self.chunk_timeout
        )
        shape = (len(hashes), *src.block_shape)
        k = np.frombuffer(raw, dtype=np_dtype,
                          count=expect // np_dtype.itemsize).reshape(shape)
        v = np.frombuffer(raw, dtype=np_dtype, offset=k_bytes).reshape(shape)

        def store():
            for i, h in enumerate(hashes):
                src.store(h, k[i], v[i], parent=parents[i])

        # tier stores do host memcpy (+ possible disk cascade): off the
        # event loop past the same small-read threshold the pull path uses
        if payload <= (256 << 10) and getattr(src, "disk", None) is None:
            store()
        else:
            await asyncio.get_running_loop().run_in_executor(None, store)
        self.checkpoint_pushes += 1
        self.checkpoint_blocks_received += len(hashes)
        if self.kvbm_distributed is not None:
            self.kvbm_distributed.note_checkpoint_received(hashes)
        await self._send_header(writer, {"ok": True, "stored": len(hashes)})

    async def _send_header(self, writer, header: dict):
        body = msgpack.packb(header, use_bin_type=True)
        writer.write(_HDR.pack(_MAGIC, len(body)) + body)
        await writer.drain()

    async def _stream(self, staged: _Staged, writer: asyncio.StreamWriter):
        desc = staged.desc
        # prefetch pipeline depth 1: extract chunk i+1 while chunk i drains
        # into the socket — the extract (device gather + host read) overlaps
        # the network transfer
        np_dtype = _np_dtype(desc.dtype)

        async def get(off: int):
            n = min(desc.chunk_pages, desc.n_pages - off)
            # streamed staging: hold until the producer commits these pages
            # (no-op for fully-staged transfers)
            await staged.wait_pages(off + n)
            k, v = await staged.extract(off, n, False)
            return off, n, np.asarray(k, np_dtype), np.asarray(v, np_dtype)

        nxt = asyncio.ensure_future(get(0)) if desc.n_pages else None
        while nxt is not None:
            off, n, k, v = await nxt
            f = faults.FAULTS
            if f.enabled and await f.on("kv_transfer.chunk") == "sever":
                # partial transfer: abort mid-stream so the peer sees a
                # broken pull (same surface as the reaped-deadline path)
                # and falls back to local prefill / retries
                raise RuntimeError("injected: kv transfer severed mid-stream")
            if staged.finished:
                # the reaper unstaged us (deadline hit) and the pages may
                # already be reused: abort mid-stream so the peer sees a
                # broken transfer instead of a "successful" corrupted one
                raise RuntimeError("transfer reaped mid-stream")
            after = off + n
            nxt = asyncio.ensure_future(get(after)) if after < desc.n_pages else None
            kb, vb = _np_bytes(k), _np_bytes(v)
            await self._send_header(
                writer,
                {"off": off, "n": n, "k_bytes": len(kb), "v_bytes": len(vb)},
            )
            writer.write(kb)
            writer.write(vb)
            # a peer that stops reading must not pin pages: deadline the drain
            await asyncio.wait_for(writer.drain(), self.chunk_timeout)
            self.bytes_served += len(kb) + len(vb)
            # a progressing transfer earns its keep — refresh the deadline so
            # slow-but-alive links are not reaped mid-pull
            staged.deadline = time.monotonic() + self.max_transfer_time
        await self._send_header(writer, {"eof": True})


class _ConnPool:
    """Keep-alive client connections to peer data planes. kvbm block
    pulls are request-response at ADMISSION rate — paying a TCP connect
    per onboarded request is pure overhead, so finished connections
    return to a small per-addr pool (the server keeps ranged/kvbm
    connections open, closing idle ones at its chunk timeout). Pools are
    scoped PER EVENT LOOP (weak-keyed): a connection created under one
    asyncio.run can never be handed to another loop, and a dead loop's
    pool drops with it."""

    def __init__(self, per_addr: int = 4):
        import weakref

        self._pools: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self.per_addr = per_addr

    def _free_map(self) -> Dict[str, list]:
        loop = asyncio.get_running_loop()
        pools = self._pools.get(loop)
        if pools is None:
            pools = {}
            self._pools[loop] = pools
        return pools

    def evict(self, addr: str):
        """Close every pooled connection to `addr` (stale-server retry:
        the whole pool is suspect, not just the one that failed)."""
        for reader, writer in self._free_map().pop(addr, []):
            writer.close()

    async def acquire(self, addr: str, connect_timeout: float,
                      fresh: bool = False):
        """Returns (reader, writer, reused). `fresh=True` bypasses (and
        evicts) the pool — the retry path after a stale keep-alive, where
        popping another pooled connection would likely be just as stale."""
        if fresh:
            self.evict(addr)
        else:
            free = self._free_map().get(addr)
            while free:
                reader, writer = free.pop()
                if writer.is_closing():
                    continue
                return reader, writer, True
        host, port = addr.rsplit(":", 1)
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, int(port)), connect_timeout
            )
        except (OSError, TimeoutError, asyncio.TimeoutError) as e:
            # gaierror/refused/unroutable: the advertised addr stopped
            # resolving — typed so callers fall back instead of crashing
            raise KvTransferError(
                f"kv data plane {addr} unreachable: {e}"
            ) from e
        _set_nodelay(writer)
        return reader, writer, False

    def release(self, addr: str, reader, writer):
        if writer.is_closing():
            return
        free = self._free_map().setdefault(addr, [])
        if len(free) >= self.per_addr:
            writer.close()
        else:
            free.append((reader, writer))


_CONN_POOL = _ConnPool()


# inject(page_offset, n_pages, k, v) — awaited per chunk as it lands
InjectFn = Callable[[int, int, Any, Any], Awaitable[None]]


async def pull_kv_range(
    addr: str,
    transfer_id: str,
    off: int,
    n: int,
    page_shape: list,
    dtype: str,
    connect_timeout: float = 10.0,
    chunk_timeout: float = 30.0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fetch ONE chunk [off, off+n) of a staged transfer — the multi-host
    shard path: decode host h pulls its own shard's chunk from prefill host
    h's data plane, so no host ever hauls another host's bytes (the scaling
    property NIXL's point-to-point descriptors give the reference,
    lib/llm/src/block_manager/storage/nixl.rs). Returns (k, v) shaped
    [L, n, page, KH, D] (the SHARD's shape)."""
    staged = _LOCAL.get((addr, transfer_id))
    if staged is not None:
        staged.deadline = time.monotonic() + staged.max_transfer_time
        k, v = await staged.extract(off, n, True)
        np_dtype = _np_dtype(dtype)
        k, v = np.asarray(k, np_dtype), np.asarray(v, np_dtype)
        # mirror the socket path's accounting: the staging host DID serve
        # these bytes, even though they never touched a socket
        staged.count_serve(k.nbytes + v.nbytes)
        return k, v
    host, port = addr.rsplit(":", 1)
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection(host, int(port)), connect_timeout
    )
    try:
        body = msgpack.packb({"tid": transfer_id, "off": off, "n": n}, use_bin_type=True)
        writer.write(_HDR.pack(_MAGIC_RANGE, len(body)) + body)
        await writer.drain()
        np_dtype = _np_dtype(dtype)
        shape = tuple(page_shape)
        max_bytes = int(np.prod(shape)) * np_dtype.itemsize * n
        hdr = await asyncio.wait_for(reader.readexactly(_HDR.size), chunk_timeout)
        magic, length = _HDR.unpack(hdr)
        if magic != _MAGIC or length > 65536:
            raise RuntimeError(f"bad kv range frame (magic {magic:#x})")
        header = msgpack.unpackb(
            await asyncio.wait_for(reader.readexactly(length), chunk_timeout),
            raw=False,
        )
        if header.get("error"):
            raise RuntimeError(f"kv range refused: {header['error']}")
        if header["k_bytes"] > max_bytes or header["v_bytes"] > max_bytes:
            raise RuntimeError("kv range frame larger than requested")
        k_raw = await asyncio.wait_for(reader.readexactly(header["k_bytes"]), chunk_timeout)
        v_raw = await asyncio.wait_for(reader.readexactly(header["v_bytes"]), chunk_timeout)
        chunk_shape = (shape[0], n, *shape[1:])
        k = np.frombuffer(k_raw, dtype=np_dtype).reshape(chunk_shape)
        v = np.frombuffer(v_raw, dtype=np_dtype).reshape(chunk_shape)
        return k, v
    finally:
        writer.close()


async def pull_kvbm_blocks(
    addr: str,
    hashes: Sequence[int],
    block_shape: tuple,
    dtype,
    connect_timeout: float = 10.0,
    chunk_timeout: float = 30.0,
    kv_format: str = "none",
) -> Tuple[np.ndarray, np.ndarray]:
    """Fetch tiered KV blocks by hash from a peer worker's data plane
    (distributed KVBM onboard; reference block_manager/distributed/
    worker.rs:137). Returns (k, v) stacked [n, *block_shape]. Raises
    KeyError on a block miss, KvTransferError on any transport failure
    (unreachable peer, severed stream) — both convert to recompute in the
    onboard path — and KvFormatError when the peer's tiers hold a
    DIFFERENT quantized page format (`kv_format` travels in the
    handshake; a mixed-precision fleet fails typed, never misreads
    packed rows). Connections come from a keep-alive pool; a stale pooled
    connection (server idled it out) earns exactly one fresh retry."""
    f = faults.FAULTS
    for attempt in (0, 1):
        reader, writer, reused = await _CONN_POOL.acquire(
            addr, connect_timeout, fresh=attempt > 0
        )
        try:
            body = msgpack.packb(
                {"blocks": [int(h) for h in hashes], "fmt": str(kv_format)},
                use_bin_type=True,
            )
            writer.write(_HDR.pack(_MAGIC_RANGE, len(body)) + body)
            await writer.drain()
            if f.enabled and await f.on("kv_transfer.pull") == "sever":
                # mid-peer-onboard sever (dynochaos): the request is on
                # the wire but we drop the connection before the payload
                # lands — the onboard path must fall back to local-tier/
                # recompute with a counted fallback, never a hung or
                # corrupted stream
                raise KvTransferError("injected: kvbm peer pull severed")
            np_dtype = np.dtype(dtype)
            expect = int(np.prod(block_shape)) * np_dtype.itemsize * len(hashes)
            hdr = await asyncio.wait_for(reader.readexactly(_HDR.size), chunk_timeout)
            magic, length = _HDR.unpack(hdr)
            if magic != _MAGIC or length > 65536:
                raise RuntimeError(f"bad kvbm frame (magic {magic:#x})")
            header = msgpack.unpackb(
                await asyncio.wait_for(reader.readexactly(length), chunk_timeout),
                raw=False,
            )
            if header.get("error"):
                # protocol-level refusal: the connection is still good
                _CONN_POOL.release(addr, reader, writer)
                if header.get("fmt_mismatch"):
                    raise KvFormatError(
                        f"kvbm peer {addr} serves kv_format="
                        f"{header.get('fmt')!r}, we run {kv_format!r}"
                    )
                raise KeyError(f"kvbm pull refused: {header['error']}")
            if header["k_bytes"] > expect or header["v_bytes"] > expect:
                raise RuntimeError("kvbm frame larger than expected")
            # k and v are contiguous on the wire: one read, split by offset
            raw = await asyncio.wait_for(
                reader.readexactly(header["k_bytes"] + header["v_bytes"]),
                chunk_timeout,
            )
            shape = (len(hashes), *block_shape)
            k = np.frombuffer(
                raw, dtype=np_dtype, count=header["k_bytes"] // np_dtype.itemsize
            ).reshape(shape)
            v = np.frombuffer(
                raw, dtype=np_dtype, offset=header["k_bytes"]
            ).reshape(shape)
            _CONN_POOL.release(addr, reader, writer)
            return k, v
        except (KeyError, KvFormatError):
            raise
        except (ConnectionError, asyncio.IncompleteReadError,
                TimeoutError, asyncio.TimeoutError) as e:
            writer.close()
            if reused and attempt == 0:
                continue  # stale keep-alive: the server idled it out
            raise KvTransferError(f"kvbm peer pull from {addr} failed: {e}") from e
        except BaseException:
            writer.close()
            raise


async def push_checkpoint_blocks(
    addr: str,
    hashes: Sequence[int],
    parents: Sequence[Optional[int]],
    k: np.ndarray,
    v: np.ndarray,
    kv_format: str = "none",
    connect_timeout: float = 2.0,
    chunk_timeout: float = 30.0,
) -> int:
    """Push session-checkpoint blocks into a peer's G2 (the replication
    half of durable decode sessions, kvbm/checkpoint.py). `k`/`v` are
    stacked [n, *block_shape] host rows in this worker's kv_format; the
    peer refuses a format mismatch typed (KvFormatError) before any byte
    is interpreted. Returns the number of blocks the peer stored. Raises
    KvTransferError on transport failure (the checkpointer quarantines
    the peer and drops the batch — replication is best-effort)."""
    k = np.ascontiguousarray(k)
    v = np.ascontiguousarray(v)
    for attempt in (0, 1):
        reader, writer, reused = await _CONN_POOL.acquire(
            addr, connect_timeout, fresh=attempt > 0
        )
        try:
            body = msgpack.packb(
                {"ckpt": {
                    "blocks": [int(h) for h in hashes],
                    "parents": [None if p is None else int(p) for p in parents],
                    "fmt": str(kv_format),
                    "k_bytes": int(k.nbytes),
                    "v_bytes": int(v.nbytes),
                }},
                use_bin_type=True,
            )
            writer.write(_HDR.pack(_MAGIC_RANGE, len(body)) + body)
            writer.write(_np_bytes(k))
            writer.write(_np_bytes(v))
            await asyncio.wait_for(writer.drain(), chunk_timeout)
            hdr = await asyncio.wait_for(reader.readexactly(_HDR.size), chunk_timeout)
            magic, length = _HDR.unpack(hdr)
            if magic != _MAGIC or length > 65536:
                raise RuntimeError(f"bad checkpoint reply (magic {magic:#x})")
            header = msgpack.unpackb(
                await asyncio.wait_for(reader.readexactly(length), chunk_timeout),
                raw=False,
            )
            if header.get("error"):
                _CONN_POOL.release(addr, reader, writer)
                if header.get("fmt_mismatch"):
                    raise KvFormatError(
                        f"checkpoint peer {addr} holds kv_format="
                        f"{header.get('fmt')!r}, we push {kv_format!r}"
                    )
                err = KvTransferError(
                    f"checkpoint push refused: {header['error']}"
                )
                # structural refusal (no kvbm tier there, block-geometry
                # mismatch): the caller excludes the peer durably instead
                # of TTL-quarantining; peer_blameless (our own oversized
                # batch) means the healthy peer must not be penalized in
                # ANY role — drop + count only
                err.ckpt_ineligible = bool(header.get("ckpt_ineligible"))
                err.peer_blameless = bool(header.get("peer_blameless"))
                raise err
            _CONN_POOL.release(addr, reader, writer)
            return int(header.get("stored") or 0)
        except (KvFormatError, KvTransferError):
            raise
        except (ConnectionError, asyncio.IncompleteReadError,
                TimeoutError, asyncio.TimeoutError) as e:
            writer.close()
            if reused and attempt == 0:
                continue  # stale keep-alive: one fresh retry
            raise KvTransferError(
                f"checkpoint push to {addr} failed: {e}"
            ) from e
        except BaseException:
            writer.close()
            raise


async def finish_transfer(
    addr: str, transfer_id: str, connect_timeout: float = 10.0
) -> None:
    """Tell the staging peer a range-pulled transfer is complete so its
    pages release immediately (the TTL reaper is the backstop)."""
    staged = _LOCAL.get((addr, transfer_id))
    if staged is not None:
        _LOCAL.pop((addr, transfer_id), None)
        staged.finish(True)
        return
    host, port = addr.rsplit(":", 1)
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection(host, int(port)), connect_timeout
    )
    try:
        body = msgpack.packb({"tid": transfer_id, "fin": True}, use_bin_type=True)
        writer.write(_HDR.pack(_MAGIC_RANGE, len(body)) + body)
        await writer.drain()
        await asyncio.wait_for(reader.readexactly(_HDR.size), connect_timeout)
    finally:
        writer.close()


async def pull_kv(
    desc: KvTransferDescriptor,
    inject: InjectFn,
    connect_timeout: float = 10.0,
    chunk_timeout: float = 30.0,
) -> None:
    """Decode-side pull: stream chunks from the staging peer and inject each
    while the rest are still in flight. Raises on any failure (caller falls
    back to local prefill). In-process transfers short-circuit through the
    local registry and stay on device."""
    staged = _LOCAL.get((desc.addr, desc.transfer_id))
    if staged is not None and not staged.started:
        staged.started = True
        staged.deadline = time.monotonic() + staged.max_transfer_time
        try:
            off = 0
            while off < desc.n_pages:
                if staged.finished:
                    raise KvTransferError("transfer reaped mid-pull")
                n = min(desc.chunk_pages, desc.n_pages - off)
                # streamed staging: the producer is still prefilling —
                # hold at its watermark (no-op when fully staged)
                await staged.wait_pages(off + n)
                k, v = await staged.extract(off, n, True)
                if staged.failed or staged.finished:
                    # producer aborted while we extracted (its pages may
                    # be recycled): never inject the chunk
                    raise KvTransferError("transfer aborted mid-pull")
                await inject(off, n, k, v)
                if hasattr(k, "nbytes"):
                    staged.count_serve(k.nbytes + v.nbytes)
                off += n
                staged.deadline = time.monotonic() + staged.max_transfer_time
            if staged.failed:
                raise KvTransferError("transfer aborted mid-pull")
        except BaseException:
            staged.finish(False)
            raise
        finally:
            _LOCAL.pop((desc.addr, desc.transfer_id), None)
        staged.finish(True)
        return

    if desc.streamed:
        # producer-paced: chunks arrive as prefill commits pages, so the
        # inter-chunk gap is bounded by the producer's liveness budget,
        # not the plain network chunk timeout
        chunk_timeout = max(chunk_timeout, 120.0)
    host, port = desc.addr.rsplit(":", 1)
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, int(port)), connect_timeout
        )
    except (OSError, TimeoutError, asyncio.TimeoutError) as e:
        # gaierror/refused/unroutable: the advertised addr stopped
        # resolving — typed so callers fall back instead of crashing
        raise KvTransferError(f"kv data plane {desc.addr} unreachable: {e}") from e
    try:
        tid = desc.transfer_id.encode()
        writer.write(_HDR.pack(_MAGIC, len(tid)) + tid)
        await writer.drain()
        np_dtype = _np_dtype(desc.dtype)
        shape = tuple(desc.page_shape)
        # every frame size the peer sends is checked against what the
        # descriptor implies — a misbehaving peer cannot force a huge alloc
        max_chunk_bytes = (
            int(np.prod(shape)) * np_dtype.itemsize * max(desc.chunk_pages, 1)
        )
        while True:
            hdr = await asyncio.wait_for(reader.readexactly(_HDR.size), chunk_timeout)
            magic, length = _HDR.unpack(hdr)
            if magic != _MAGIC:
                raise RuntimeError(f"bad kv frame magic {magic:#x}")
            if length > 65536:
                raise RuntimeError(f"oversized kv frame header ({length} bytes)")
            header = msgpack.unpackb(
                await asyncio.wait_for(reader.readexactly(length), chunk_timeout),
                raw=False,
            )
            if header.get("error"):
                raise RuntimeError(f"kv transfer refused: {header['error']}")
            if header.get("eof"):
                return
            off, n = header["off"], header["n"]
            if not (0 <= off and 0 < n <= desc.chunk_pages and off + n <= desc.n_pages):
                raise RuntimeError(f"kv chunk out of range (off={off} n={n})")
            if header["k_bytes"] > max_chunk_bytes or header["v_bytes"] > max_chunk_bytes:
                raise RuntimeError(
                    f"kv frame larger than descriptor allows ({header['k_bytes']})"
                )
            k_raw = await asyncio.wait_for(
                reader.readexactly(header["k_bytes"]), chunk_timeout
            )
            v_raw = await asyncio.wait_for(
                reader.readexactly(header["v_bytes"]), chunk_timeout
            )
            chunk_shape = (shape[0], n, *shape[1:])
            k = np.frombuffer(k_raw, dtype=np_dtype).reshape(chunk_shape)
            v = np.frombuffer(v_raw, dtype=np_dtype).reshape(chunk_shape)
            await inject(off, n, k, v)
    finally:
        writer.close()
