"""Disaggregated prefill/decode: KV page transfer (serving/disagg.py).

Covers the wire format (bit-identical round trips for f32 and
int8+scales, through pickle AND a real socket boundary), the
pool_to_pages -> bytes -> pages_to_pool cross-pool round trip, the
engine export/import seams (a transferred prefix makes the target
engine's streams byte-identical to a colocated engine), and the
graftlint hot-path coverage of the transfer path (seeded violation).
"""

import os
import pickle
import socket
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.disagg import (
    KVPageTransfer, deserialize_kv_transfer, page_geometry,
    serialize_kv_transfer)
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import PagePool
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PS = 8


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def make_engine(params, **over):
    cfg = dict(max_batch_size=2, max_seq_len=256, page_size=PS,
               prefill_buckets=(16, 32), prefix_cache=True,
               pace_emission_max_streams=0)
    cfg.update(over)
    return LLMEngine(params, TINY, ByteTokenizer(), EngineConfig(**cfg),
                     use_pallas=False)


def _random_pool(dtype, n_pages=6):
    rng = np.random.default_rng(7)
    pool = PagePool.zeros(TINY, n_pages, PS, dtype=dtype)
    if pool.quantized:
        kv = rng.integers(-127, 128, pool.kv.shape, np.int8)
        s = rng.random(pool.s.shape, np.float32)
        return type(pool)(jnp.asarray(kv), jnp.asarray(s), PS)
    k = rng.standard_normal(pool.k.shape).astype(pool.k.dtype)
    v = rng.standard_normal(pool.v.shape).astype(pool.v.dtype)
    return PagePool(jnp.asarray(k), jnp.asarray(v), PS)


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

class TestWireFormat:
    def _roundtrip(self, buf):
        ids, codes, scales = deserialize_kv_transfer(buf)
        return ids, codes, scales

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_serialize_roundtrip_bit_identical(self, dtype):
        rng = np.random.default_rng(3)
        cshape, cdtype, sshape = page_geometry(_random_pool(dtype))
        n = 3
        if cdtype == np.int8:
            codes = rng.integers(-127, 128, (n,) + cshape, np.int8)
        else:
            codes = rng.standard_normal((n,) + cshape).astype(cdtype)
        scales = (rng.random((n,) + sshape, np.float32)
                  if sshape else None)
        ids = list(range(n * PS))
        buf = serialize_kv_transfer(ids, codes, scales)
        got_ids, got_codes, got_scales = self._roundtrip(buf)
        assert got_ids == ids
        assert got_codes.dtype == codes.dtype
        np.testing.assert_array_equal(got_codes, codes)
        if scales is None:
            assert got_scales is None
        else:
            np.testing.assert_array_equal(got_scales, scales)

    def test_payload_survives_pickle_and_socket(self):
        """The cross-process contract: the byte payload (pickled, then
        pushed through a real socketpair) reconstructs bit-identical
        arrays — no dtype/endianness/shape drift at a process
        boundary."""
        rng = np.random.default_rng(5)
        cshape, cdtype, sshape = page_geometry(_random_pool("int8"))
        codes = rng.integers(-127, 128, (2,) + cshape, np.int8)
        scales = rng.random((2,) + sshape, np.float32)
        buf = pickle.loads(pickle.dumps(
            serialize_kv_transfer([1] * 2 * PS, codes, scales)))
        a, b = socket.socketpair()
        try:
            def send():
                a.sendall(buf)
                a.shutdown(socket.SHUT_WR)

            t = threading.Thread(target=send)
            t.start()
            chunks = []
            while True:
                c = b.recv(65536)
                if not c:
                    break
                chunks.append(c)
            t.join()
        finally:
            a.close()
            b.close()
        ids, got_codes, got_scales = deserialize_kv_transfer(
            b"".join(chunks))
        assert ids == [1] * 2 * PS
        np.testing.assert_array_equal(got_codes, codes)
        np.testing.assert_array_equal(got_scales, scales)

    def test_bad_magic_rejected(self):
        with pytest.raises(ValueError):
            deserialize_kv_transfer(b"nope" + b"\x00" * 64)

    def test_truncated_payload_raises_value_error(self):
        """Garbled/truncated payloads must surface as ValueError (the
        import endpoint's 422), whatever the underlying parse error
        (struct.error on a cut header, short array bytes, ...)."""
        cshape, cdtype, sshape = page_geometry(_random_pool("int8"))
        codes = np.zeros((2,) + cshape, np.int8)
        scales = np.zeros((2,) + sshape, np.float32)
        full = serialize_kv_transfer([1] * 2 * PS, codes, scales)
        for cut in (7, 12, len(full) // 2):
            with pytest.raises(ValueError):
                deserialize_kv_transfer(full[:cut])

    def _full_payload(self):
        cshape, _, sshape = page_geometry(_random_pool("int8"))
        codes = np.zeros((2,) + cshape, np.int8)
        scales = np.zeros((2,) + sshape, np.float32)
        return serialize_kv_transfer([1] * 2 * PS, codes, scales)

    def test_truncated_preamble_names_the_preamble(self):
        with pytest.raises(ValueError, match="preamble"):
            deserialize_kv_transfer(b"GKVT1\x10")

    def test_header_overclaiming_length_rejected(self):
        """A header-length field claiming more bytes than the buffer
        holds must fail the length check, not read past the end."""
        import struct as _struct

        buf = b"GKVT1" + _struct.pack("<I", 10_000) + b"{}"
        with pytest.raises(ValueError, match="header claims"):
            deserialize_kv_transfer(buf)

    @pytest.mark.parametrize("header", [
        b"not json at all",            # undecodable
        b"[1, 2, 3]",                  # wrong JSON type
        b'{"n_ids": 4}',               # missing fields
        b'{"n_ids": -1, "codes_dtype": "int8", "codes_shape": [1],'
        b' "scales_shape": null}',     # negative dimension
        b'{"n_ids": 1, "codes_dtype": "no_such_dtype",'
        b' "codes_shape": [1], "scales_shape": null}',  # unknown dtype
    ])
    def test_rotten_header_fields_rejected_with_offset(self, header):
        import struct as _struct

        buf = b"GKVT1" + _struct.pack("<I", len(header)) + header
        with pytest.raises(ValueError,
                           match="malformed KV transfer header at offset"):
            deserialize_kv_transfer(buf)

    def test_short_body_reports_offset_and_section(self):
        """A body cut mid-codes must name the starved section and the
        offset — the sender's framing bug should be findable from the
        one error string."""
        full = self._full_payload()
        with pytest.raises(ValueError,
                           match=r"short KV transfer body: \w+ needs "
                                 r"\d+ bytes at offset \d+"):
            deserialize_kv_transfer(full[: len(full) - 100])

    def test_trailing_bytes_rejected(self):
        with pytest.raises(ValueError, match="trailing"):
            deserialize_kv_transfer(self._full_payload() + b"\x00\x01")

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_pool_to_pages_bytes_pages_to_pool_roundtrip(self, dtype):
        """The full transfer data path across two POOLS: gather pages
        from a source pool, serialize, deserialize, scatter into a
        zeroed target pool — the target's pages must be bit-identical
        to the source's (codes AND int8 scales verbatim)."""
        src = _random_pool(dtype)
        dst = PagePool.zeros(TINY, 6, PS, dtype=dtype)
        rows = [2, 4, 5]
        row = jnp.asarray(np.array(rows, np.int32))
        codes, scales = engine_model.pool_to_pages(src, row)
        buf = serialize_kv_transfer(list(range(len(rows) * PS)),
                                    np.asarray(codes),
                                    None if scales is None
                                    else np.asarray(scales))
        _, got_codes, got_scales = deserialize_kv_transfer(buf)
        dst = engine_model.pages_to_pool(
            dst, jnp.asarray(got_codes),
            None if got_scales is None else jnp.asarray(got_scales),
            row)
        if src.quantized:
            np.testing.assert_array_equal(
                np.asarray(dst.kv[:, :, :, rows]),
                np.asarray(src.kv[:, :, :, rows]))
            np.testing.assert_array_equal(
                np.asarray(dst.s[:, :, :, rows]),
                np.asarray(src.s[:, :, :, rows]))
        else:
            np.testing.assert_array_equal(
                np.asarray(dst.k[:, :, rows]),
                np.asarray(src.k[:, :, rows]))
            np.testing.assert_array_equal(
                np.asarray(dst.v[:, :, rows]),
                np.asarray(src.v[:, :, rows]))


# ---------------------------------------------------------------------------
# engine export / import seams
# ---------------------------------------------------------------------------

class TestEngineTransfer:
    def _greedy(self, eng, prompt, max_new=12):
        return [ev["token_id"] for ev in
                eng.generate_stream(list(prompt), max_new_tokens=max_new)
                if ev["token_id"] >= 0]

    def test_export_import_transfers_prefix_and_streams_match(self,
                                                              params):
        """e1 prefills a prompt; its pages export, import into e2;
        e2's greedy stream equals a colocated engine's, with e2's
        admission scoring a real prefix hit (zero re-prefill of the
        transferred prefix)."""
        prompt = [(3 * j) % 250 + 1 for j in range(26)]  # 3 full pages
        ref = make_engine(params).start()
        want = self._greedy(ref, prompt)
        ref.stop()

        e1 = make_engine(params).start()
        self._greedy(e1, prompt, max_new=1)  # prefill + cache insert
        out = e1.run_control_op(lambda: e1.export_prefix_pages(prompt))
        e1.stop()
        assert out is not None
        codes, scales, n_tokens = out
        assert n_tokens == (len(prompt) // PS) * PS
        assert codes.shape[0] == len(prompt) // PS

        e2 = make_engine(params).start()
        n = e2.run_control_op(
            lambda: e2.import_prefix_pages(prompt, codes, scales))
        assert n == codes.shape[0]
        assert e2.prefix_cache.n_cached_pages == n
        got = self._greedy(e2, prompt)
        assert got == want
        assert e2.metrics.prefix_hits == 1
        snap = e2.metrics.snapshot()
        assert snap["kv_transfer_pages"] == n
        assert snap["kv_transfer_ms"] > 0
        assert snap["hist_kv_transfer_ms_per_page"]["count"] == 1
        e2.stop()

    def test_import_ships_only_nonresident_suffix(self, params):
        """A growing multi-turn prefix re-imports every turn; the
        target must allocate/scatter only the chunks it does NOT
        already hold (re-shipping a 1000-page conversation for a
        one-page tail would reclaim-evict hot cache for nothing)."""
        turn1 = [(3 * j) % 250 + 1 for j in range(2 * PS)]
        turn2 = turn1 + [(5 * j) % 250 + 1 for j in range(2 * PS)]
        e1 = make_engine(params).start()
        e2 = make_engine(params).start()
        try:
            self._greedy(e1, turn2, max_new=1)  # caches all 4 pages
            codes, scales, _ = e1.run_control_op(
                lambda: e1.export_prefix_pages(turn2))
            # Seed the target with turn 1's two pages only.
            n1 = e2.run_control_op(
                lambda: e2.import_prefix_pages(turn1, codes[:2],
                                               None if scales is None
                                               else scales[:2]))
            assert n1 == 2
            # Full-prefix import now moves ONLY the tail.
            n2 = e2.run_control_op(
                lambda: e2.import_prefix_pages(turn2, codes, scales))
            assert n2 == 2
            assert e2.metrics.kv_transfer_pages == 4
            assert e2.prefix_cache.n_cached_pages == 4
            # ...and the full path still serves byte-identically.
            ref = make_engine(params).start()
            want = self._greedy(ref, turn2)
            ref.stop()
            assert self._greedy(e2, turn2) == want
        finally:
            e1.stop()
            e2.stop()

    def test_import_already_resident_is_noop(self, params):
        prompt = [(5 * j) % 250 + 1 for j in range(18)]  # 2 full pages
        e1 = make_engine(params).start()
        self._greedy(e1, prompt, max_new=1)
        codes, scales, _ = e1.run_control_op(
            lambda: e1.export_prefix_pages(prompt))
        # Importing into the engine that already holds the prefix
        # moves nothing (and allocates nothing it keeps).
        n = e1.run_control_op(
            lambda: e1.import_prefix_pages(prompt, codes, scales))
        assert n == 0
        assert e1.metrics.kv_transfer_pages == 0
        e1.stop()

    def test_export_nothing_cached_returns_none(self, params):
        eng = make_engine(params)
        assert eng.export_prefix_pages([1, 2, 3]) is None

    def test_control_op_runs_inline_when_stopped(self, params):
        eng = make_engine(params)
        assert eng.run_control_op(lambda: 41 + 1) == 42

    def test_control_op_propagates_errors(self, params):
        eng = make_engine(params).start()
        try:
            with pytest.raises(RuntimeError, match="boom"):
                eng.run_control_op(
                    lambda: (_ for _ in ()).throw(RuntimeError("boom")))
        finally:
            eng.stop()

    def test_kvpagetransfer_moves_between_local_replicas(self, params):
        from generativeaiexamples_tpu.serving.fleet import LocalReplica

        prompt = [(7 * j) % 250 + 1 for j in range(20)]
        e1, e2 = make_engine(params).start(), make_engine(params).start()
        try:
            self._greedy(e1, prompt, max_new=1)
            pages, ms = KVPageTransfer().transfer(
                LocalReplica("a", e1), LocalReplica("b", e2), prompt)
            assert pages == len(prompt) // PS
            assert ms > 0
            assert e2.prefix_cache.n_cached_pages == pages
        finally:
            e1.stop()
            e2.stop()

    def test_export_window_matches_full_export_slice(self, params):
        """The window contract: export_prefix_pages(start_page,
        max_pages) returns exactly the full export's page slice, and
        its n_tokens covers the prefix THROUGH the window's end."""
        prompt = [(11 * j) % 250 + 1 for j in range(4 * PS)]
        e1 = make_engine(params).start()
        try:
            self._greedy(e1, prompt, max_new=1)
            full_codes, full_scales, full_n = e1.run_control_op(
                lambda: e1.export_prefix_pages(prompt))
            assert full_n == 4 * PS
            for start, width in ((0, 2), (1, 1), (2, 0), (3, 2)):
                out = e1.run_control_op(
                    lambda s=start, w=width: e1.export_prefix_pages(
                        prompt, start_page=s, max_pages=w))
                assert out is not None
                codes, scales, n_tokens = out
                end = min(4, start + width) if width else 4
                assert n_tokens == end * PS
                np.testing.assert_array_equal(
                    np.asarray(codes), np.asarray(full_codes[start:end]))
                if full_scales is not None:
                    np.testing.assert_array_equal(
                        np.asarray(scales),
                        np.asarray(full_scales[start:end]))
            # A window past the cached prefix is empty, not an error.
            assert e1.run_control_op(
                lambda: e1.export_prefix_pages(prompt, start_page=4,
                                               max_pages=2)) is None
        finally:
            e1.stop()

    def test_chunked_import_equals_one_shot(self, params):
        """Two first_page-offset chunk imports seat the same prefix as
        one monolithic import — same cached pages, byte-identical
        stream — and a chunk GAP raises instead of corrupting."""
        prompt = [(13 * j) % 250 + 1 for j in range(4 * PS)]
        e1 = make_engine(params).start()
        one = make_engine(params).start()
        two = make_engine(params).start()
        try:
            self._greedy(e1, prompt, max_new=1)
            codes, scales, _ = e1.run_control_op(
                lambda: e1.export_prefix_pages(prompt))
            sl = (lambda a, lo, hi: None if a is None else a[lo:hi])
            n_one = one.run_control_op(
                lambda: one.import_prefix_pages(prompt, codes, scales))
            n_a = two.run_control_op(
                lambda: two.import_prefix_pages(
                    prompt[: 2 * PS], codes[:2], sl(scales, 0, 2)))
            n_b = two.run_control_op(
                lambda: two.import_prefix_pages(
                    prompt, codes[2:], sl(scales, 2, 4), first_page=2))
            assert (n_a, n_b) == (2, 2)
            assert n_one == 4
            assert two.prefix_cache.n_cached_pages \
                == one.prefix_cache.n_cached_pages == 4
            assert two.metrics.kv_transfer_chunks == 2
            assert self._greedy(two, prompt) == self._greedy(one, prompt)
            # Gap: seating pages [3..) while only [0..1) is resident.
            three = make_engine(params).start()
            try:
                three.run_control_op(
                    lambda: three.import_prefix_pages(
                        prompt[:PS], codes[:1], sl(scales, 0, 1)))
                with pytest.raises(ValueError, match="gap"):
                    three.run_control_op(
                        lambda: three.import_prefix_pages(
                            prompt, codes[3:], sl(scales, 3, 4),
                            first_page=3))
            finally:
                three.stop()
        finally:
            e1.stop()
            one.stop()
            two.stop()

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_device_path_bit_identical_to_host_bounce(self, kv_dtype):
        """The acceptance pin: the device route and the GKVT host
        bounce seat bit-identical pool bytes (re-exporting from each
        target compares codes AND scales), and the device route's
        stream equals the colocated one."""
        from generativeaiexamples_tpu.serving.fleet import LocalReplica

        p = llama.init_params(TINY, jax.random.PRNGKey(0))
        prompt = [(7 * j) % 250 + 1 for j in range(3 * PS)]
        src = make_engine(p, kv_dtype=kv_dtype).start()
        via_dev = make_engine(p, kv_dtype=kv_dtype).start()
        via_host = make_engine(p, kv_dtype=kv_dtype).start()
        try:
            want = self._greedy(src, prompt)
            a = LocalReplica("a", src)
            dev_pages, _ = KVPageTransfer(device_path=True).transfer(
                a, LocalReplica("b", via_dev), prompt)
            host_pages, _ = KVPageTransfer().transfer(
                a, LocalReplica("c", via_host), prompt)
            assert dev_pages == host_pages == 3
            assert via_dev.metrics.kv_transfer_device_pages == 3
            assert via_host.metrics.kv_transfer_device_pages == 0
            dc, ds, _ = via_dev.run_control_op(
                lambda: via_dev.export_prefix_pages(prompt))
            hc, hs, _ = via_host.run_control_op(
                lambda: via_host.export_prefix_pages(prompt))
            np.testing.assert_array_equal(np.asarray(dc), np.asarray(hc))
            if ds is not None:
                np.testing.assert_array_equal(np.asarray(ds),
                                              np.asarray(hs))
            assert self._greedy(via_dev, prompt) == want
        finally:
            src.stop()
            via_dev.stop()
            via_host.stop()

    def test_publish_prefill_pages_coverage(self, params):
        """publish_prefill_pages reports (and makes transferable) the
        covered full-page prefix: 0 for an unknown prompt, the full
        page count once the prompt is cached, and monotone non-
        decreasing values when polled against a live engine."""
        prompt = [(17 * j) % 250 + 1 for j in range(10 * PS)]
        eng = make_engine(params).start()
        try:
            assert eng.run_control_op(
                lambda: eng.publish_prefill_pages(prompt)) == 0
            seen = []
            req_stream = eng.generate_stream(list(prompt),
                                             max_new_tokens=4)
            for ev in req_stream:
                seen.append(eng.run_control_op(
                    lambda: eng.publish_prefill_pages(prompt)))
            assert seen == sorted(seen)  # coverage only grows
            assert eng.run_control_op(
                lambda: eng.publish_prefill_pages(prompt)) == 10
            # The published prefix is really in the tree: a repeat
            # serve takes the prefix hit.
            before = eng.metrics.prefix_hits
            self._greedy(eng, prompt, max_new=2)
            assert eng.metrics.prefix_hits == before + 1
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# pipelined fleet + process replica lifecycle
# ---------------------------------------------------------------------------

class TestPipelinedFleet:
    def _fleet_greedy(self, fleet, prompt, max_new=12):
        from generativeaiexamples_tpu.serving.engine import GenRequest

        req = GenRequest(prompt_ids=list(prompt), max_new_tokens=max_new)
        fleet.submit(req)
        toks = []
        while True:
            ev = req.stream.get(timeout=180)
            if ev["token_id"] >= 0:
                toks.append(ev["token_id"])
            if ev["finished"]:
                return toks

    def test_pipelined_disagg_byte_identical_and_chunked(self, params):
        """The tentpole e2e: a pipelined 1-page-chunk disagg fleet
        serves byte-identically to a colocated engine, the transfer
        really was windowed (chunks > plans), and decode admission
        beat the final chunk (early admits counted)."""
        from generativeaiexamples_tpu.serving.fleet import (
            EngineFleet, LocalReplica)

        prompts = [[(7 * i + j) % 250 + 1 for j in range(3 * PS + 2 * i)]
                   for i in range(3)]
        ref = make_engine(params).start()
        want = [self._greedy_single(ref, p) for p in prompts]
        ref.stop()
        reps = [LocalReplica("r0", make_engine(params), role="prefill"),
                LocalReplica("r1", make_engine(params), role="decode")]
        fleet = EngineFleet(reps, ByteTokenizer(), PS, disagg=True,
                            disagg_pipeline=True,
                            disagg_transfer_chunk_pages=1).start()
        try:
            got = [self._fleet_greedy(fleet, p) for p in prompts]
            snap = fleet.metrics.snapshot()
            assert got == want
            assert snap["router_disagg_plans"] == len(prompts)
            assert snap["kv_transfer_chunks"] \
                > snap["router_disagg_plans"]
            assert snap["disagg_early_admits"] > 0
            assert snap["disagg_fallbacks"] == 0
            assert snap["disagg_transfer_ms"] > 0
        finally:
            fleet.stop()

    def _greedy_single(self, eng, prompt, max_new=12):
        return [ev["token_id"] for ev in
                eng.generate_stream(list(prompt), max_new_tokens=max_new)
                if ev["token_id"] >= 0]

    def test_pipeline_off_is_serialized_plan(self, params):
        """disagg_pipeline=False (the default) never chunks and never
        early-admits — the PR-14 serialized plan, pinned so the
        default stays byte-identical in behavior AND counters."""
        from generativeaiexamples_tpu.serving.fleet import (
            EngineFleet, LocalReplica)

        prompt = [(5 * j) % 250 + 1 for j in range(3 * PS)]
        reps = [LocalReplica("r0", make_engine(params), role="prefill"),
                LocalReplica("r1", make_engine(params), role="decode")]
        fleet = EngineFleet(reps, ByteTokenizer(), PS,
                            disagg=True).start()
        try:
            self._fleet_greedy(fleet, prompt)
            snap = fleet.metrics.snapshot()
            assert snap["router_disagg_plans"] == 1
            assert snap["disagg_early_admits"] == 0
            assert snap["kv_transfer_chunks"] == 1  # one window
        finally:
            fleet.stop()

    def test_ship_async_drain(self):
        """drain() waits for background tail ships; a failing tail is
        logged, counted down, and never raises into the caller."""
        class _SlowSrc:
            rid = "s"

            def export_kv_pages(self, ids, timeout_s=0, start_page=0,
                                max_pages=0):
                import time as _t

                _t.sleep(0.05)
                return None  # nothing cached: window empty

        class _Dst:
            rid = "d"

        mover = KVPageTransfer()
        mover.ship_async(_SlowSrc(), _Dst(), [1, 2, 3], 0)
        assert mover.drain(timeout_s=10.0)
        assert mover._inflight == 0

    def test_process_replica_stop_terminates_subprocess(self):
        import subprocess
        import sys as _sys

        from generativeaiexamples_tpu.serving.fleet import ProcessReplica

        proc = subprocess.Popen(
            [_sys.executable, "-c", "import time; time.sleep(600)"])
        rep = ProcessReplica("p0", "http://127.0.0.1:1", proc,
                             probe_timeout_s=0.1)
        try:
            assert proc.poll() is None
            rep.stop()
            assert proc.poll() is not None
            rep.stop()  # idempotent
            # A dead process fails healthy() without an HTTP probe.
            assert not rep.healthy()
        finally:
            if proc.poll() is None:
                proc.kill()


# ---------------------------------------------------------------------------
# graftlint hot-path coverage of the transfer path
# ---------------------------------------------------------------------------

class TestLintCoverage:
    def test_hot_path_markers_cover_transfer_path(self, tmp_path):
        """The transfer/placement path carries `# graftlint: hot-path`
        markers, so GL401 covers it: a seeded blocking host sync
        inside a marked transfer method is flagged, and the shipped
        module itself stays clean."""
        from generativeaiexamples_tpu.lint import lint_paths

        src_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "generativeaiexamples_tpu",
            "serving", "disagg.py")
        with open(src_path) as fh:
            src = fh.read()
        bad = src + textwrap.dedent("""

        class _SeededBadTransfer(KVPageTransfer):
            # graftlint: hot-path
            def hack(self):
                return np.asarray(self.dev_staging)  # blocking sync
        """)
        mod = tmp_path / "disagg.py"
        mod.write_text(bad)
        findings = [f for f in lint_paths([str(mod)])
                    if f.check == "GL401"]
        assert any("dev_staging" in f.message or "asarray" in f.message
                   for f in findings)
        # ...and the shipped transfer module is clean.
        assert not [f for f in lint_paths([src_path])
                    if f.check in ("GL401", "GL402")]

    def test_place_disagg_and_fleet_transfer_are_declared_hot(self):
        """The satellite contract: the placement + transfer entry
        points are DECLARED hot (HOT_ROOTS or an explicit marker), so
        the interprocedural host-sync checks scan them."""
        import ast

        from generativeaiexamples_tpu.lint.checks.host_sync import (
            declared_hot)
        from generativeaiexamples_tpu.lint.core import SourceFile

        base = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "generativeaiexamples_tpu",
            "serving")
        want = {"router.py": {"place_disagg"},
                "fleet.py": {"_submit_disagg", "_run_disagg_stages",
                             "_run_disagg_pipelined",
                             "export_kv_pages", "import_kv_pages",
                             "publish_kv_pages",
                             "export_kv_pages_device",
                             "import_kv_pages_device"},
                "disagg.py": {"transfer", "transfer_window",
                              "_ship_tail"}}
        for fname, fns in want.items():
            path = os.path.join(base, fname)
            with open(path) as fh:
                source = fh.read()
            tree = ast.parse(source)
            sf = SourceFile(path, rel=fname, source=source, tree=tree,
                            lines=source.splitlines())
            found = {}
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef):
                    found[node.name] = node
            for fn in fns:
                if fn not in found:
                    continue  # e.g. _submit_disagg folded elsewhere
                assert declared_hot(sf, found[fn]), \
                    f"{fname}:{fn} lost its hot-path marker"

    def test_gl202_covers_transfer_state_lock(self, tmp_path):
        """GL202 watches the mover's thread model: a seeded sibling of
        KVPageTransfer whose background-thread write to shared state
        is locked but whose public read is NOT gets flagged, and the
        shipped module itself stays GL202-quiet (every access of the
        pair memo / in-flight count takes self._lock)."""
        from generativeaiexamples_tpu.lint import lint_paths

        src_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "generativeaiexamples_tpu",
            "serving", "disagg.py")
        with open(src_path) as fh:
            src = fh.read()
        bad = src + textwrap.dedent("""

        class _SeededRacyMover:
            def __init__(self):
                self._lock = threading.Lock()
                self.shipped = 0

            def start(self):
                threading.Thread(target=self._pump).start()

            def _pump(self):
                with self._lock:
                    self.shipped += 1

            def progress(self):
                return self.shipped  # unlocked cross-thread read
        """)
        mod = tmp_path / "disagg.py"
        mod.write_text(bad)
        findings = [f for f in lint_paths([str(mod)])
                    if f.check == "GL202" and "shipped" in f.message]
        assert findings, "seeded unlocked cross-thread read not flagged"
        # ...and the shipped transfer module's lock discipline holds.
        assert not [f for f in lint_paths([src_path])
                    if f.check == "GL202"]
