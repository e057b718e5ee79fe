"""Multi-host runtime units: fetch seams, dispatch log, replay lockstep.

Single-process tests — the 2-process integration path is gated by
scripts/smoke_multihost.py; here the contracts are pinned with stub
clients and spec'd mock arrays (a real cross-process shard cannot exist
in one pytest process).
"""

from unittest import mock

import jax
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig, MeshConfig
from generativeaiexamples_tpu.serving import multihost as mh


# ---------------------------------------------------------------------------
# fetch seams
# ---------------------------------------------------------------------------


def test_fetch_passthrough_on_plain_and_local_arrays():
    x = np.arange(6).reshape(2, 3)
    np.testing.assert_array_equal(mh.fetch_replicated(x, "t"), x)
    np.testing.assert_array_equal(mh.fetch_addressable(x, "t"), x)
    j = jax.numpy.arange(4)  # single-process: fully addressable
    np.testing.assert_array_equal(mh.fetch_replicated(j, "t"), np.arange(4))
    np.testing.assert_array_equal(mh.fetch_addressable(j, "t"), np.arange(4))


def _mock_array(shape, dtype=np.int32, *, replicated, shards, index_map):
    """A spec'd jax.Array mock: passes isinstance, exposes exactly the
    attributes the fetch seams read."""
    arr = mock.MagicMock(spec=jax.Array)
    arr.shape = shape
    arr.dtype = np.dtype(dtype)
    arr.is_fully_addressable = False
    arr.is_fully_replicated = replicated
    mocked = []
    for index, data in shards:
        sh = mock.Mock()
        sh.index = index
        sh.data = data
        mocked.append(sh)
    arr.addressable_shards = mocked
    arr.sharding.devices_indices_map.return_value = index_map
    return arr


def test_fetch_replicated_rejects_cross_process_shards():
    arr = _mock_array((4,), replicated=False, shards=[], index_map={})
    with pytest.raises(mh.MultihostFetchError, match="token readback"):
        mh.fetch_replicated(arr, "token readback")


def test_fetch_addressable_assembles_local_coverage():
    lo, hi = (slice(0, 2, None),), (slice(2, 4, None),)
    arr = _mock_array(
        (4,), replicated=False,
        shards=[(lo, np.array([1, 2], np.int32)),
                (hi, np.array([3, 4], np.int32))],
        index_map={"dev0": lo, "dev1": hi})
    np.testing.assert_array_equal(mh.fetch_addressable(arr, "gather"),
                                  np.array([1, 2, 3, 4], np.int32))


def test_fetch_addressable_names_missing_remote_shards():
    lo, hi = (slice(0, 2, None),), (slice(2, 4, None),)
    arr = _mock_array((4,), replicated=False,
                      shards=[(lo, np.array([1, 2], np.int32))],
                      index_map={"dev0": lo, "remote-dev": hi})
    with pytest.raises(mh.MultihostFetchError,
                       match="page export.*remote processes"):
        mh.fetch_addressable(arr, "page export")


def test_fetch_slice_passthrough_on_plain_and_local_arrays():
    x = np.arange(6, dtype=np.int32).reshape(2, 3)
    out, idx = mh.fetch_addressable_slice(x, "t")
    np.testing.assert_array_equal(out, x)
    assert idx == (slice(0, 2), slice(0, 3))
    j = jax.numpy.arange(4)  # single-process: fully addressable
    out, idx = mh.fetch_addressable_slice(j, "t")
    np.testing.assert_array_equal(out, np.arange(4))
    assert idx == (slice(0, 4),)


def test_fetch_slice_assembles_local_block_and_global_index():
    """Local shards covering rows 2:4 come back as one contiguous
    block plus the global slice it occupies — the pager's per-host
    demote contract."""
    a = (slice(2, 3, None), slice(0, 6, None))
    b = (slice(3, 4, None), slice(0, 6, None))
    arr = _mock_array(
        (8, 6), replicated=False,
        shards=[(a, np.full((1, 6), 7, np.int32)),
                (b, np.full((1, 6), 9, np.int32))],
        index_map={})
    out, idx = mh.fetch_addressable_slice(arr, "pager demote")
    assert idx == (slice(2, 4), slice(0, 6))
    np.testing.assert_array_equal(
        out, np.concatenate([np.full((1, 6), 7), np.full((1, 6), 9)]))


def test_fetch_slice_rejects_non_contiguous_local_shards():
    a = (slice(0, 1, None), slice(0, 6, None))
    b = (slice(2, 3, None), slice(0, 6, None))
    arr = _mock_array(
        (8, 6), replicated=False,
        shards=[(a, np.zeros((1, 6), np.int32)),
                (b, np.zeros((1, 6), np.int32))],
        index_map={})
    with pytest.raises(mh.MultihostFetchError,
                       match="do not tile a contiguous block"):
        mh.fetch_addressable_slice(arr, "pager demote")


def test_put_local_slice_roundtrips_single_process():
    j = jax.numpy.arange(12, dtype=jax.numpy.int32).reshape(3, 4)
    local, idx = mh.fetch_addressable_slice(j, "t")
    back = mh.put_local_slice(local, idx, j.shape, j.sharding)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(j))


def test_put_local_slice_rejects_mismatched_coverage():
    j = jax.numpy.arange(12, dtype=jax.numpy.int32).reshape(3, 4)
    with pytest.raises(mh.MultihostError, match="does not match"):
        mh.put_local_slice(np.zeros((1, 4), np.int32),
                           (slice(1, 2), slice(0, 4)),
                           j.shape, j.sharding)


# ---------------------------------------------------------------------------
# dispatch log
# ---------------------------------------------------------------------------


class _StubClient:
    """coordination-service KV stand-in: string store + deadline error
    on missing keys (matching blocking_key_value_get semantics)."""

    def __init__(self):
        self.kv = {}

    def key_value_set(self, k, v):
        self.kv[k] = v

    def blocking_key_value_get(self, k, timeout_ms):
        if k not in self.kv:
            raise RuntimeError("Deadline Exceeded")
        return self.kv[k]


def test_encode_decode_roundtrip():
    payload = {"tokens": np.arange(12, dtype=np.int32).reshape(3, 4),
               "temps": np.zeros(3, np.float32),
               "k": np.int32(7)}
    kind, out = mh._decode(mh._encode("prefill", payload))
    assert kind == "prefill"
    assert set(out) == set(payload)
    for k in payload:
        np.testing.assert_array_equal(out[k], payload[k])
    assert mh._decode(mh._encode("stop", {})) == ("stop", {})


def test_dispatch_log_orders_and_times_out():
    client = _StubClient()
    pub = mh.DispatchLog(client=client)
    sub = mh.DispatchLog(client=client)
    pub.publish("prefill", tokens=np.array([1, 2]))
    pub.publish("decode", k=np.int32(4))
    kind0, rec0 = sub.next_record(timeout_s=1)
    kind1, rec1 = sub.next_record(timeout_s=1)
    assert kind0 == "prefill" and list(rec0["tokens"]) == [1, 2]
    assert kind1 == "decode" and int(rec1["k"]) == 4
    with pytest.raises(mh.MultihostError, match="leader gone"):
        sub.next_record(timeout_s=0.05, poll_s=0.02)


def test_run_follower_replays_until_stop():
    client = _StubClient()
    pub = mh.DispatchLog(client=client)
    pub.publish("prefill", a=np.int32(1))
    pub.publish("plan", b=np.int32(2))
    pub.publish("stop")  # flushes the final digest first

    calls = []

    class _Eng:
        _mh_log = mh.DispatchLog(client=client)

        def _mh_replay_table(self):
            return {
                "prefill": lambda rec: calls.append(
                    ("prefill", int(rec["a"]))),
                "plan": lambda rec: calls.append(("plan", int(rec["b"]))),
            }

    mh.run_follower(_Eng(), timeout_s=1)
    assert calls == [("prefill", 1), ("plan", 2)]


def test_run_follower_rejects_unknown_kind_and_unbuilt_engine():
    client = _StubClient()
    mh.DispatchLog(client=client).publish("mystery")

    class _Eng:
        _mh_log = mh.DispatchLog(client=client)

        def _mh_replay_table(self):
            return {}

    with pytest.raises(mh.MultihostError, match="mystery"):
        mh.run_follower(_Eng(), timeout_s=1)

    class _Plain:
        _mh_log = None

    with pytest.raises(mh.MultihostError, match="multihost=true"):
        mh.run_follower(_Plain())


# ---------------------------------------------------------------------------
# divergence detector
# ---------------------------------------------------------------------------


def _tampered_stream():
    """A 2-record stream whose second record was swapped after the
    leader CRC'd it — the digest that rides ahead of `stop` must name
    exactly that record."""
    client = _StubClient()
    pub = mh.DispatchLog(client=client)
    pub.publish("prefill", a=np.int32(1))
    pub.publish("plan", b=np.int32(2))
    client.kv["gaiemh/000000001"] = mh._encode("plan", {"b": np.int32(99)})
    pub.publish("stop")
    return client


def test_divergence_detector_names_key_and_kind():
    sub = mh.DispatchLog(client=_tampered_stream())
    assert sub.next_record(timeout_s=1)[0] == "prefill"
    assert sub.next_record(timeout_s=1)[0] == "plan"  # tampered, reads fine
    with pytest.raises(
            mh.MultihostDivergenceError,
            match=r"gaiemh/000000001.*kind 'plan'"):
        sub.next_record(timeout_s=1)  # hits the digest before `stop`


def test_run_follower_counts_divergence_and_reraises():
    client = _tampered_stream()

    class _Metrics:
        replay_divergence = 0

    class _Eng:
        _mh_log = mh.DispatchLog(client=client)
        metrics = _Metrics()

        def _mh_replay_table(self):
            return {"prefill": lambda rec: None, "plan": lambda rec: None}

    eng = _Eng()
    with pytest.raises(mh.MultihostDivergenceError):
        mh.run_follower(eng, timeout_s=1)
    assert eng.metrics.replay_divergence == 1


def test_clean_stream_verifies_at_stop():
    """The digest ahead of `stop` verifies silently on an untampered
    stream (and digest records never surface to the caller)."""
    client = _StubClient()
    pub = mh.DispatchLog(client=client)
    for i in range(5):
        pub.publish("plan", b=np.int32(i))
    pub.publish("stop")
    sub = mh.DispatchLog(client=client)
    kinds = [sub.next_record(timeout_s=1)[0] for _ in range(6)]
    assert kinds == ["plan"] * 5 + ["stop"]


# ---------------------------------------------------------------------------
# profile validation
# ---------------------------------------------------------------------------


def test_profile_accepts_full_feature_set():
    """The generalized record vocabulary replays the whole serving
    feature set — the config that PR 17 rejected now validates."""
    ecfg = EngineConfig(speculative_k=2, speculative_tree_branches=2,
                        step_plans=True, fused_prefill=True,
                        fused_sampling=True, prefix_cache=True,
                        kv_pager=True)
    mh.validate_multihost_profile(ecfg)  # must not raise


def test_acceptance_table_and_rejections_cover_lint_catalog():
    """MULTIHOST_ACCEPTED citations plus the one remaining rejection
    (batch-sharded mesh) cover exactly the registered GL70x catalog —
    so the acceptance table, the rejection text, and the lint family
    cannot drift apart. Accepted names must be real EngineConfig
    fields."""
    import dataclasses
    import re

    from generativeaiexamples_tpu.lint.checks import ALL_CHECKS

    class _Mesh:  # duck-typed: validate only reads mesh.shape.get
        shape = {"data": 2, "fsdp": 1, "tensor": 2}

    with pytest.raises(mh.MultihostError) as ei:
        mh.validate_multihost_profile(EngineConfig(), _Mesh())
    rej_ids = set(re.findall(r"GL70\d", str(ei.value)))
    acc_ids = {cid for _, cid, _ in mh.MULTIHOST_ACCEPTED}
    catalog = {c.id for c in ALL_CHECKS if c.id.startswith("GL70")}
    assert acc_ids | rej_ids == catalog, (acc_ids, rej_ids, catalog)
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    assert {name for name, _, _ in mh.MULTIHOST_ACCEPTED} <= fields


def test_profile_rejects_batch_sharded_mesh(eight_devices):
    from generativeaiexamples_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(ici_data=2, ici_tensor=4))
    with pytest.raises(mh.MultihostError, match="data axis = 2"):
        mh.validate_multihost_profile(EngineConfig(), mesh)
    mh.validate_multihost_profile(
        EngineConfig(), build_mesh(MeshConfig(ici_tensor=8)))


# ---------------------------------------------------------------------------
# replay lockstep: a second engine fed only the dispatch records ends in
# the leader's exact device state
# ---------------------------------------------------------------------------


def _tiny_engine(params, cfg, **overrides):
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    ecfg = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                        prefill_buckets=(16,),
                        pace_emission_max_streams=0,
                        **overrides)
    return LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                     use_pallas=False)


def test_replay_reproduces_leader_device_state():
    """Leader serves real requests while publishing records to a stub
    log; a fresh engine replaying ONLY those records (never seeing a
    request) ends with byte-identical last-token chain and KV pool —
    the invariant the cross-process follower relies on."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import GenRequest

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    client = _StubClient()

    leader = _tiny_engine(params, cfg)
    leader._mh_log = mh.DispatchLog(client=client)
    leader._mh_leader = True
    leader.start()
    for i in range(2):
        req = GenRequest(prompt_ids=[(7 * i + j) % 250 + 1
                                     for j in range(10)],
                         max_new_tokens=6)
        leader.submit(req)
        while True:
            ev = req.stream.get(timeout=120)
            if ev["finished"]:
                break
    leader.stop()  # publishes the stop record

    follower = _tiny_engine(params, cfg)
    follower._mh_log = mh.DispatchLog(client=client)
    mh.run_follower(follower, timeout_s=5)

    np.testing.assert_array_equal(np.asarray(leader._last_tokens),
                                  np.asarray(follower._last_tokens))
    np.testing.assert_array_equal(np.asarray(leader.pool.k),
                                  np.asarray(follower.pool.k))
    np.testing.assert_array_equal(np.asarray(leader.pool.v),
                                  np.asarray(follower.pool.v))
    follower.stop()


def _serve_and_replay(prompts, concurrent=False, **features):
    """Leader serves `prompts` (list of (ids, max_new)) with `features`
    on, publishing to a stub log; a fresh follower engine replays the
    records. `concurrent` submits everything up front (decode traffic
    overlaps long prefills — the fused-rider lane). Returns (leader,
    follower) for state comparison — both already stopped."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import GenRequest

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    client = _StubClient()

    leader = _tiny_engine(params, cfg, **features)
    leader._mh_log = mh.DispatchLog(client=client)
    leader._mh_leader = True
    if leader.kv_pager is not None:
        leader.kv_pager.mh_log = leader._mh_log
    leader.start()

    def _serve(batch):
        reqs = [GenRequest(prompt_ids=list(ids), max_new_tokens=max_new)
                for ids, max_new in batch]
        for req in reqs:
            leader.submit(req)
        for req in reqs:
            while True:
                ev = req.stream.get(timeout=120)
                if ev["finished"]:
                    break

    if concurrent:
        _serve(prompts)
    else:
        for p in prompts:
            _serve([p])
    leader.stop()  # publishes the stop record

    follower = _tiny_engine(params, cfg, **features)
    follower._mh_log = mh.DispatchLog(client=client)
    mh.run_follower(follower, timeout_s=5)
    follower.stop()
    return leader, follower


def _assert_device_state_identical(leader, follower, spec=False):
    np.testing.assert_array_equal(np.asarray(leader._last_tokens),
                                  np.asarray(follower._last_tokens))
    np.testing.assert_array_equal(np.asarray(leader.pool.k),
                                  np.asarray(follower.pool.k))
    np.testing.assert_array_equal(np.asarray(leader.pool.v),
                                  np.asarray(follower.pool.v))
    if spec:
        np.testing.assert_array_equal(np.asarray(leader._history),
                                      np.asarray(follower._history))
        np.testing.assert_array_equal(np.asarray(leader._dev_lengths),
                                      np.asarray(follower._dev_lengths))


def test_replay_speculative_tree_with_step_plans():
    """Spec-tree + step-plan serving: every plan-lattice point the
    scheduler picks (plain decode, spec draft/verify, tree verify,
    spec-state refresh) rides the plan record and replays to
    byte-identical device state INCLUDING the draft history/length
    arrays the next speculation round reads."""
    prompts = [([(7 * i + j) % 250 + 1 for j in range(10)], 6)
               for i in range(2)]
    leader, follower = _serve_and_replay(
        prompts, speculative_k=2, speculative_tree_branches=2,
        step_plans=True)
    assert leader.metrics.spec_slot_steps > 0
    _assert_device_state_identical(leader, follower, spec=True)


def test_replay_fused_prefill_prefix_cache_and_pager():
    """Chunked fused prefill (prompt > largest bucket) with fused
    sampling, then the SAME prompt again for a warm prefix hit (the
    pool_to_cache seed record) — followers replay the rider chunks,
    the fused-sample commit, and the seed gather byte-identically,
    with the kv pager wired into the record stream."""
    ids = [(3 * j) % 250 + 1 for j in range(40)]  # > 16-token bucket
    leader, follower = _serve_and_replay(
        [(ids, 4), (ids, 4)], fused_prefill=True, fused_sampling=True,
        step_plans=True, prefix_cache=True, kv_pager=True)
    assert leader.metrics.prefix_hits > 0  # turn 2 reused turn 1's pages
    assert leader.metrics.fused_sample_dispatches > 0
    _assert_device_state_identical(leader, follower)


def test_replay_fused_rider_on_decode():
    """A short prompt decoding WHILE a long prompt prefills: the long
    prompt's chunks ride inside decode dispatches (fused_decode_prefill
    plan points) and the follower replays the combined launches."""
    short = ([5, 6, 7, 8], 24)
    long = ([(3 * j) % 250 + 1 for j in range(40)], 4)
    leader, follower = _serve_and_replay(
        [short, long], concurrent=True,
        fused_prefill=True, step_plans=True)
    assert leader.metrics.fused_steps > 0
    _assert_device_state_identical(leader, follower)
