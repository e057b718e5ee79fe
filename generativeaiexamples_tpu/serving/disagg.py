"""Disaggregated prefill/decode: KV page transfer between replicas.

The DistServe/Mooncake serving shape (OSDI'24): prefill-heavy work and
decode beats have opposite resource profiles — a long prefill is one
huge compute burst that serializes ahead of every decode block on the
same device queue, while decode wants steady short beats. The fleet
therefore specializes replicas by ROLE (`fleet.replica_roles`):
"prefill" replicas run chunked/fused prefill stages only and never
receive decode placements, "decode"/"mixed" replicas serve normal
traffic. `PrefixLocalityRouter.place_disagg` emits the two-stage plan
(prefill replica -> decode replica), and this module moves the
finished prefill's KV pages between them.

Transfer paths, selected per window by `KVPageTransfer`:

* **device path** (ICI fast path): when both replicas' engines are
  process-addressable on one slice (LocalReplicas — the CPU/dev shape
  of a shared-ICI pod; the multi-host DCN leg is gated in
  parallel/mesh.py), pages move as jax.Arrays straight from the
  source's pool gather into the target's scatter — zero host
  serialization, int8 codes + f32 scales verbatim so the route is
  bit-identical to the host bounce. Any device-path failure marks the
  replica pair broken and falls back to the host bounce on the SAME
  window (counted, never fatal).
* **host bounce** (GKVT — the universal fallback, and the
  `/v1/kv/export`//`/v1/kv/import` wire for process-separated fleets):

  1. the prefill stage runs on the prefill-role replica; its completed
     prefill inserts the prompt's full pages into that replica's radix
     prefix cache (the existing admission path — nothing new runs on
     the prefill side);
  2. `export`: batched `engine_model.pool_to_pages` gathers on the
     source — chunked at the pager granularity so no single control
     op blocks on a monolithic whole-prefix gather — move the window
     device->host (a pager-demoted tail is read straight from its
     cold tier — serving/kv_pager.py `read_pages`); int8 codes +
     narrow scales travel VERBATIM, so the transfer is bit-identical
     to never having left the pool;
  3. the bytes cross the replica boundary: in-process as numpy arrays
     (LocalReplica), or serialized through `serialize_kv_transfer`
     over the replica's `/v1/kv/import` endpoint (HttpReplica);
  4. `import`: ONE `engine_model.pages_to_pool` scatter seats the
     pages on the target and the prefix enters the target's radix
     tree, so the decode submit that follows takes the NORMAL
     prefix-cache hit path — zero re-prefill of the transferred
     prefix, and later turns of the same session hit the same cache.

With `fleet.disagg_pipeline` the fleet does not wait for the whole
prefill: the source publishes completed chunks' pages mid-prefill
(`LLMEngine.publish_prefill_pages`), each covered window ships while
later chunks compute, and the FINAL window ships from a background
thread (`ship_async`) so decode admission takes its prefix-cache hit
before the last chunk lands — TTFT overlaps transfer with the prefill
tail instead of summing them. Import dedup + the `first_page` window
contract make a late or repeated chunk harmless.

Both engine halves run as scheduler-thread control ops
(`LLMEngine.run_control_op`), so the tree/allocator/pool single-owner
discipline holds across the transfer. Failures at any stage fall back
to colocated serving on the same stream (`EngineFleet._submit_disagg`)
— disagg is an optimization, never a correctness dependency, and
`fleet.disagg=false` (the default) is byte-identical to the static
fleet.

Wire format (`serialize_kv_transfer`): a fixed magic + JSON header
(shapes/dtypes/token count) followed by raw little-endian array bytes
— self-describing, picklable, and streamable through a socket without
a deserialization framework on either side.
"""

from __future__ import annotations

import json
import logging
import struct
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

_LOG = logging.getLogger(__name__)

_MAGIC = b"GKVT1"


def page_geometry(pool) -> Tuple[tuple, np.dtype, Optional[tuple]]:
    """(codes_shape, codes_dtype, scales_shape|None) of ONE page of
    `pool` in pool_to_pages' page-major layout — the shared contract
    between export, import, the KV pager and the wire format."""
    g = pool.geometry
    codes = (2, g.rows, g.kv_heads, g.page_size, g.head_dim)
    return codes, np.dtype(g.dtype), codes[:-1] if pool.quantized else None


def _resolve_dtype(name: str) -> np.dtype:
    """Dtype NAME -> np.dtype, resolving the ml_dtypes extension types
    (bfloat16 & friends) that plain np.dtype(...) may not know — the
    default engine KV dtype is bfloat16, and its legacy ``.str`` form
    is an unreconstructible void ("|V2")."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def serialize_kv_transfer(ids: Sequence[int], codes: np.ndarray,
                          scales: Optional[np.ndarray]) -> bytes:
    """Pack one transfer (prompt ids + page-major KV bytes) into a
    self-describing buffer: magic | u32 header len | JSON header |
    int32 ids | codes bytes | scales bytes. Codes/scales are exactly
    pool_to_pages' layout, moved verbatim (never re-quantized).
    Dtypes travel by NAME ("bfloat16", "float32", "int8") so the
    ml_dtypes extension types reconstruct; multi-byte types are
    little-endian on the wire (every supported platform is)."""
    codes = np.ascontiguousarray(codes)
    header = {
        "n_ids": len(ids),
        "codes_dtype": codes.dtype.name,
        "codes_shape": list(codes.shape),
        "scales_shape": (list(scales.shape) if scales is not None
                         else None),
    }
    hb = json.dumps(header).encode()
    parts = [_MAGIC, struct.pack("<I", len(hb)), hb,
             np.asarray(list(ids), np.int32).tobytes(), codes.tobytes()]
    if scales is not None:
        parts.append(np.ascontiguousarray(scales, np.float32).tobytes())
    return b"".join(parts)


def deserialize_kv_transfer(buf: bytes) -> Tuple[List[int], np.ndarray,
                                                 Optional[np.ndarray]]:
    """Inverse of serialize_kv_transfer -> (ids, codes, scales). The
    arrays are reconstructed bit-identical (the round-trip test pins
    this for f32 and int8+scales through a socket boundary).

    The buffer arrives off a network endpoint, so every length is
    validated BEFORE any numpy reshape touches it: truncated,
    oversized and garbage payloads all raise ValueError with the
    offending offset — the import endpoint answers 422 bad_kv_payload
    instead of a reshape crash polluting the availability signal.
    Trailing bytes are an error too (a framing bug upstream, not
    padding)."""
    total = len(buf)
    pre = len(_MAGIC) + 4
    if total < pre:
        raise ValueError(
            f"truncated KV transfer payload: {total} bytes is shorter "
            f"than the {pre}-byte magic + header-length preamble")
    if buf[: len(_MAGIC)] != _MAGIC:
        raise ValueError("not a KV transfer payload (bad magic)")
    off = len(_MAGIC)
    (hlen,) = struct.unpack_from("<I", buf, off)
    off += 4
    if hlen > total - off:
        raise ValueError(
            f"malformed KV transfer payload: header claims {hlen} "
            f"bytes at offset {off} but only {total - off} remain")
    try:
        header = json.loads(buf[off: off + hlen].decode())
        if not isinstance(header, dict):
            raise TypeError(f"header is {type(header).__name__}, "
                            "expected object")
        n_ids = int(header["n_ids"])
        codes_dtype = _resolve_dtype(str(header["codes_dtype"]))
        codes_shape = tuple(int(d) for d in header["codes_shape"])
        raw_ss = header["scales_shape"]
        scales_shape = (None if raw_ss is None
                        else tuple(int(d) for d in raw_ss))
        if n_ids < 0 or any(d < 0 for d in codes_shape) or (
                scales_shape is not None
                and any(d < 0 for d in scales_shape)):
            raise TypeError("negative dimension")
    except Exception as e:
        # Garbage headers surface as JSONDecodeError / KeyError /
        # TypeError / AttributeError (unknown dtype name) depending
        # on which field is rotten — normalize with the offset so the
        # sender can find the framing bug.
        raise ValueError(
            f"malformed KV transfer header at offset {off}: "
            f"{type(e).__name__}: {e}") from e
    off += hlen

    def take(count: int, dtype: np.dtype, what: str) -> np.ndarray:
        nonlocal off
        need = count * dtype.itemsize
        have = total - off
        if have < need:
            raise ValueError(
                f"short KV transfer body: {what} needs {need} bytes "
                f"at offset {off}, only {have} remain")
        arr = np.frombuffer(buf, dtype, count=count, offset=off)
        off += need
        return arr

    ids = take(n_ids, np.dtype(np.int32), "ids").tolist()
    n_codes = int(np.prod(codes_shape, dtype=np.int64))
    codes = take(n_codes, codes_dtype,
                 "codes").reshape(codes_shape).copy()
    scales = None
    if scales_shape is not None:
        n_scales = int(np.prod(scales_shape, dtype=np.int64))
        scales = take(n_scales, np.dtype(np.float32),
                      "scales").reshape(scales_shape).copy()
    if off != total:
        raise ValueError(
            f"oversized KV transfer payload: {total - off} trailing "
            f"bytes after offset {off}")
    return ids, codes, scales


class KVPageTransfer:
    """Page mover between two fleet replicas: per-window transport
    selection (device path when both engines are process-addressable
    on one slice, GKVT host bounce otherwise — see the module
    docstring's matrix), optional chunking, and the background
    tail-ship that lets decode admission overtake the last chunk.
    The fleet owns fallback-to-colocated policy; `ops` (FleetOps,
    optional) receives the device-fallback count.

    `transfer` returns (pages_imported, wall_ms) — 0 pages with no
    exception means the source had nothing cached (the caller falls
    back) or the target already held the prefix (success: the decode
    submit hits the cache either way).

    Thread model: `transfer`/`transfer_window` run on fleet submit
    threads; `_ship_tail` runs on its own background thread. The
    transfer state they share — the per-pair device-health memo and
    the in-flight tail count `drain()` waits on — lives behind
    ``self._lock`` (a Condition: drain waits on it too) on every
    access."""

    def __init__(self, timeout_s: float = 60.0, chunk_pages: int = 0,
                 device_path: bool = False, ops=None):
        self.timeout_s = float(timeout_s)
        # Pages per window when the fleet chunks a transfer (0 = one
        # window, the PR-14 shape).
        self.chunk_pages = max(0, int(chunk_pages))
        self.device_path = bool(device_path)
        self.ops = ops
        # THE transfer-state lock (see the class docstring's thread
        # model): a Condition so drain() can wait on the in-flight
        # count under the same lock that guards it — one lock, no
        # ordering to get wrong (and graftlint GL202 verifies every
        # shared access takes it).
        self._lock = threading.Condition()
        # (src_rid, dst_rid) pairs whose device path failed once:
        # every later window goes straight to the host bounce — a
        # flapping fast path must not pay the exception per chunk.
        self._device_broken: set = set()
        self._inflight = 0  # background tail ships not yet landed

    # graftlint: hot-path
    def transfer(self, src, dst, ids: Sequence[int],
                 page_size: int = 0) -> Tuple[int, float]:
        """Export `ids`' cached prefix from `src` and import it into
        `dst` (replica objects with export_kv_pages/import_kv_pages).
        With `chunk_pages` set (and `page_size` known) the prefix
        moves window by window — each window one bounded export +
        import control-op pair — otherwise in one window, exactly the
        PR-14 behavior. Raises on stage failure — the fleet maps that
        to the colocated fallback."""
        t0 = time.perf_counter()
        total = 0
        if self.chunk_pages and page_size:
            start = 0
            while True:
                imported, end_tokens = self.transfer_window(
                    src, dst, ids, start, self.chunk_pages)
                total += imported
                end_page = end_tokens // page_size
                if end_page <= start:
                    break  # window empty: prefix exhausted
                start = end_page
        else:
            total, _ = self.transfer_window(src, dst, ids, 0, 0)
        return total, (time.perf_counter() - t0) * 1e3

    # graftlint: hot-path
    def transfer_window(self, src, dst, ids: Sequence[int],
                        start_page: int = 0, max_pages: int = 0
                        ) -> Tuple[int, int]:
        """Move ONE page window [start_page, start_page+max_pages) of
        `ids`' cached prefix (max_pages<=0: through the end). Tries
        the device path first when enabled and the pair qualifies; a
        device failure marks the pair broken, counts the fallback,
        and re-ships the SAME window over the host bounce — transport
        trouble is never a stream failure. Returns (pages_imported,
        end_tokens) where end_tokens is the prefix covered through
        the window's end — (0, 0) when the window is empty."""
        if self.device_path and self.device_ok(src, dst):
            try:
                got = self._window_device(src, dst, ids, start_page,
                                          max_pages)
                if got is not None:
                    return got
            except Exception as e:
                with self._lock:
                    self._device_broken.add(
                        (getattr(src, "rid", ""), getattr(dst, "rid", "")))
                if self.ops is not None:
                    self.ops.note_disagg_device_fallback()
                _LOG.warning(
                    "device-path KV transfer %s->%s failed at page %d "
                    "(%s: %s); falling back to host bounce",
                    getattr(src, "rid", "?"), getattr(dst, "rid", "?"),
                    start_page, type(e).__name__, e)
        exported = src.export_kv_pages(ids, timeout_s=self.timeout_s,
                                       start_page=start_page,
                                       max_pages=max_pages)
        if exported is None:
            return 0, 0
        codes, scales, n_tokens = exported
        pages = dst.import_kv_pages(list(ids)[:n_tokens], codes, scales,
                                    timeout_s=self.timeout_s,
                                    first_page=start_page)
        return pages, n_tokens

    def _window_device(self, src, dst, ids: Sequence[int],
                       start_page: int, max_pages: int
                       ) -> Optional[Tuple[int, int]]:
        """Device leg of one window: the source's pool gather stays a
        jax.Array end to end (zero serialization); the target stages
        and scatters it on device. None when the window holds no
        device-resident pages (a pager-demoted tail — the caller's
        host bounce covers it; NOT a device failure). The device
        export caps each call at the engine's warmed gather width, so
        an uncapped window ships in several sub-windows here."""
        ps = src.transfer_page_size()
        start = end = max(0, int(start_page))
        stop = None if max_pages <= 0 else start + int(max_pages)
        total = 0
        while stop is None or end < stop:
            cap = 0 if stop is None else stop - end
            exported = src.export_kv_pages_device(
                ids, timeout_s=self.timeout_s, start_page=end,
                max_pages=cap)
            if exported is None:
                break
            codes, scales, n_tokens = exported
            total += dst.import_kv_pages_device(
                list(ids)[:n_tokens], codes, scales,
                timeout_s=self.timeout_s, first_page=end)
            new_end = n_tokens // ps
            if new_end <= end:
                break
            end = new_end
        if end == start:
            return None  # no device-resident pages in this window
        return total, end * ps

    def device_ok(self, src, dst) -> bool:
        """May this pair take the device path right now? Both replicas
        must expose the device surface (LocalReplicas; an HttpReplica
        never does — its engine lives in another process, so the wire
        is the only route), their engines' devices must be mutually
        process-addressable (parallel/mesh.py devices_colocated — the
        one-slice ICI condition), and the pair must not have failed
        the fast path before."""
        if not (hasattr(src, "export_kv_pages_device")
                and hasattr(dst, "import_kv_pages_device")
                and hasattr(src, "transfer_page_size")):
            return False
        with self._lock:
            if (getattr(src, "rid", ""),
                    getattr(dst, "rid", "")) in self._device_broken:
                return False
        from generativeaiexamples_tpu.parallel.mesh import (
            devices_colocated)

        try:
            return devices_colocated(src.transfer_device_set(),
                                     dst.transfer_device_set())
        except Exception as e:
            # A failed probe just means "host bounce" — but say why, or
            # a misconfigured mesh silently loses the fast path forever.
            _LOG.warning(
                "device-path colocation probe %s->%s failed: %s: %s",
                getattr(src, "rid", "?"), getattr(dst, "rid", "?"),
                type(e).__name__, e)
            return False

    def ship_async(self, src, dst, ids: Sequence[int],
                   start_page: int = 0) -> threading.Thread:
        """Ship the tail [start_page, end-of-prefix) from a background
        thread and return immediately — the pipelined fleet calls this
        for the FINAL window so decode admission takes its prefix-
        cache hit before the last chunk lands. Import dedup + the
        first_page contract make the late chunk harmless; a tail
        failure only costs the decode side a re-prefill of that tail
        (logged, never a stream failure). fleet.stop() drains these
        via drain()."""
        with self._lock:
            self._inflight += 1
        t = threading.Thread(target=self._ship_tail,
                             args=(src, dst, list(ids), start_page),
                             daemon=True, name="kv-tail-ship")
        t.start()
        return t

    # graftlint: hot-path
    def _ship_tail(self, src, dst, ids: List[int],
                   start_page: int) -> None:
        try:
            self.transfer_window(src, dst, ids, start_page, 0)
        except Exception as e:
            _LOG.warning("background KV tail ship at page %d failed: "
                         "%s: %s — the decode side re-prefills that "
                         "tail", start_page, type(e).__name__, e)
        finally:
            with self._lock:
                self._inflight -= 1
                self._lock.notify_all()

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Block until every background tail ship has landed (True) or
        the timeout passed (False, tails still in flight)."""
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        with self._lock:
            while self._inflight:
                wait = (1.0 if deadline is None
                        else deadline - time.monotonic())
                if wait <= 0:
                    return False
                self._lock.wait(wait)
            return True
