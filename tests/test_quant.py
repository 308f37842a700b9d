"""Quantized serving (models/quant.py, ISSUE 13): int8 weights and
int8 KV pages with in-kernel dequant.

Covers the tentpole's acceptance bar end to end on CPU tiny engines:

  * weight quantization accuracy + structure (per-channel scales, norms
    untouched, bytes ~quartered from the fp32 test params);
  * the shared KV write rule (zero-safe, max lands on ±127, requant of
    an unchanged page is deterministic);
  * KERNEL-LEVEL: in-kernel dequant (interpret-mode Pallas) vs the
    dequantize-then-attend oracle within tolerance, and the scaled
    gather reference EXACTLY equal to dequantize-then-ref;
  * quantized SELF-CONSISTENCY: quantized monolithic == quantized
    cluster == quantized wire peers, bit-identical at temp 0 for
    greedy, constrained-JSON, and speculative decoding;
  * scales travel with the pages: hibernate→restore bit-equality,
    DiskPrefixStore round trip (scales under the same crc; flipped
    scale bytes rejected + unlinked), HandoffEnvelope wire round trip
    (int8+scales preserved, truncated scale bytes a structured error),
    prefixd int8 blobs;
  * signature rules: quantized↔unquantized peers reject handoff BEFORE
    bytes move (both in-process and at the wire codec), and the
    unquantized signature is byte-identical to its pre-ISSUE-13 value;
  * pool_sizing dtype columns; /api/kv quant block; Prometheus
    exposition of the quoracle_quant_* instruments.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.quant import (
    dequant_weight, is_quantized, kv_dequant, kv_quant, kv_token_bytes,
    params_nbytes, quantize_params,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

MEMBER = "xla:tiny"
CFG = get_model_config(MEMBER)
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
MSGS = [{"role": "user", "content": "hello quantized world, please "
                                    "elaborate at length"}]


def make_engine(quant=True, **kw):
    return GenerateEngine(CFG, PARAMS, ByteTokenizer(), max_seq=512,
                          prompt_buckets=(32, 64, 128, 256),
                          quantize_weights=quant, quantize_kv=quant,
                          **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def req(msgs=MSGS, sid=None, cj=False, max_tokens=20):
    from quoracle_tpu.models.runtime import QueryRequest
    return QueryRequest(MEMBER, msgs, temperature=0.0,
                        max_tokens=max_tokens, session_id=sid,
                        constrain_json=cj)


SYS = "system: " + "policy rules apply here. " * 8    # > 1 page of 128


# ---------------------------------------------------------------------------
# Weight quantization
# ---------------------------------------------------------------------------

def test_weight_quant_structure_and_accuracy():
    qp = quantize_params(PARAMS, CFG)
    # projections quantized; norms stay dense
    assert is_quantized(qp["layers"]["wq"])
    assert is_quantized(qp["embed"])
    assert not is_quantized(qp["layers"]["attn_norm"])
    assert qp["layers"]["wq"]["q8"].dtype == jnp.int8
    assert qp["layers"]["wq"]["scale"].dtype == jnp.float32
    # per-channel symmetric: dequant error bounded by half a step per
    # channel (scale = amax/127 → max abs error ≤ scale/2)
    w = np.asarray(PARAMS["layers"]["wq"], np.float32)
    wd = np.asarray(dequant_weight(qp["layers"]["wq"], jnp.float32))
    step = np.abs(w).max(axis=-2, keepdims=True) / 127.0
    assert (np.abs(wd - w) <= step / 2 + 1e-7).all()
    # fp32 params → int8 payloads: bytes roughly quarter
    assert params_nbytes(qp) < 0.4 * params_nbytes(PARAMS)


def test_kv_quant_rule():
    x = jax.random.normal(jax.random.PRNGKey(1), (10, CFG.n_kv_heads, 16))
    q, s = kv_quant(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    # the max element of every (token, head) vector lands on ±127
    assert (np.abs(np.asarray(q)).max(axis=-1) == 127).all()
    # zero vectors quantize safely (scale 1.0, q 0)
    qz, sz = kv_quant(jnp.zeros((2, CFG.n_kv_heads, 16)))
    assert (np.asarray(qz) == 0).all() and (np.asarray(sz) == 1.0).all()
    # requantizing the dequantized page reproduces the int8 payload
    q2, _ = kv_quant(kv_dequant(q, s))
    assert (np.asarray(q) == np.asarray(q2)).all()


# ---------------------------------------------------------------------------
# Kernel-level: in-kernel dequant vs the dequantize-then-attend oracle
# ---------------------------------------------------------------------------

def test_ragged_kernel_dequant_vs_oracle():
    from quoracle_tpu.ops.paged_attention import (
        ragged_attend, ragged_attend_ref,
    )
    n_pages, page, KV, hd = 6, 8, 2, 16
    H, tq, NB = 4, 4, 2
    key = jax.random.PRNGKey(2)
    kf = jax.random.normal(key, (n_pages, page, KV, hd))
    vf = jax.random.normal(jax.random.fold_in(key, 1),
                           (n_pages, page, KV, hd))
    kq, ks = kv_quant(kf)
    vq, vs = kv_quant(vf)
    ksl = jnp.transpose(ks, (0, 2, 1))        # [n_pages, KV, page]
    vsl = jnp.transpose(vs, (0, 2, 1))

    def stored(pages, layer):
        """One layer's pages as layer ``layer`` of a 2-layer pool in the
        stored layout ([L, n_pages, page, KV·hd]; scales [L, n_pages, KV,
        page]); the other layer holds garbage."""
        flat = pages.reshape(*pages.shape[:2], -1) if pages.ndim == 4 \
            else pages
        pool = jnp.stack([flat, flat]).at[1 - layer].set(
            jnp.full_like(flat, 77))
        return pool
    tables = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    # [4, NB]: kv_len, qpos0, nq, row
    meta = jnp.array([[20, 10], [16, 6], [4, 4], [0, 1]], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (NB * tq, H, hd))
    # oracle: dequantize the pages, then attend with the plain reference
    oracle = ragged_attend_ref(q, stored(kv_dequant(kq, ks), 0),
                               stored(kv_dequant(vq, vs), 0),
                               tables, meta, 0, tq=tq)
    # scaled reference must be EXACT (same math, dequant folded in)
    ref = ragged_attend_ref(q, stored(kq, 1), stored(vq, 1), tables, meta,
                            1, tq=tq, k_scale=stored(ksl, 1),
                            v_scale=stored(vsl, 1))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                               rtol=0, atol=1e-6)
    # in-kernel dequant (interpret-mode Pallas) within tolerance
    out = ragged_attend(q, stored(kq, 1), stored(vq, 1), tables, meta, 1,
                        tq=tq, interpret=True, k_scale=stored(ksl, 1),
                        v_scale=stored(vsl, 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Quantized self-consistency: mono == cluster == wire peers
# ---------------------------------------------------------------------------

def test_quantized_mono_vs_cluster_selfconsistency():
    """The tentpole gate: quantized monolithic vs quantized
    disaggregated cluster, bit-identical at temp 0 for greedy,
    constrained-JSON and speculative decoding."""
    from quoracle_tpu.models.runtime import TPUBackend
    from quoracle_tpu.serving.cluster import ClusterPlane
    mono = TPUBackend([MEMBER], continuous_chunk=8,
                      draft_map={MEMBER: MEMBER}, draft_k=4,
                      quantize_weights=True, quantize_kv=True)
    cl = ClusterPlane.build([MEMBER], replicas=2, disaggregate=True,
                            continuous_chunk=8,
                            draft_map={MEMBER: MEMBER}, draft_k=4,
                            quantize_weights=True, quantize_kv=True)
    try:
        a = mono.query([req()])[0]
        b = cl.query([req()])[0]
        assert a.ok and b.ok, (a.error, b.error)
        assert b.text == a.text
        assert cl.handoff.exports >= 1      # the flow disaggregated
        aj = mono.query([req(cj=True, max_tokens=32)])[0]
        bj = cl.query([req(cj=True, max_tokens=32)])[0]
        assert aj.ok and bj.ok and bj.text == aj.text
        asp = mono.query([req(sid="q1", cj=True, max_tokens=24)])[0]
        bsp = cl.query([req(sid="q1", cj=True, max_tokens=24)])[0]
        assert asp.ok and bsp.ok and bsp.text == asp.text
        assert bsp.spec_rounds > 0          # decode actually drafted
        # signatures across replicas match (uniform quantization)
        sigs = {rep.backend.engines[MEMBER].kv_signature()
                for rep in cl.replicas}
        assert len(sigs) == 1 and "q8kv" in next(iter(sigs))
    finally:
        mono.close()
        cl.close()


def test_quantized_mono_vs_wire_peer_selfconsistency():
    """Quantized monolithic vs two quantized loopback fabric peers:
    the int8+scales envelope crosses the real wire codec and decode
    stays bit-identical."""
    from quoracle_tpu.models.runtime import TPUBackend
    from quoracle_tpu.serving.cluster import RemoteReplica
    from quoracle_tpu.serving.fabric.frontdoor import FabricPlane
    from quoracle_tpu.serving.fabric.peer import FabricPeer
    from quoracle_tpu.serving.fabric.transport import LoopbackTransport
    mono = TPUBackend([MEMBER], continuous_chunk=8,
                      quantize_weights=True, quantize_kv=True)
    peers = [FabricPeer.build([MEMBER], role="prefill",
                              replica_id="prefill-0", continuous_chunk=8,
                              quantize_weights=True, quantize_kv=True),
             FabricPeer.build([MEMBER], role="decode",
                              replica_id="decode-0", continuous_chunk=8,
                              quantize_weights=True, quantize_kv=True)]
    plane = FabricPlane([
        RemoteReplica(LoopbackTransport(p.handle, p.replica_id))
        for p in peers])
    try:
        a = mono.query([req()])[0]
        b = plane.query([req()])[0]
        assert a.ok and b.ok, (a.error, b.error)
        assert b.text == a.text
        assert plane.wire_handoffs >= 1     # bytes crossed the codec
        aj = mono.query([req(cj=True, max_tokens=32)])[0]
        bj = plane.query([req(cj=True, max_tokens=32)])[0]
        assert aj.ok and bj.ok and bj.text == aj.text
    finally:
        plane.close()
        for p in peers:
            p.close()
        mono.close()


# ---------------------------------------------------------------------------
# Scales travel with the pages
# ---------------------------------------------------------------------------

def test_quantized_hibernate_restore_bit_equal():
    tok = ByteTokenizer()
    p1 = enc(SYS + " task: count to five.")
    ctl = make_engine()
    a1 = ctl.generate([p1], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])
    p2 = p1 + a1[0].token_ids + tok.encode(" continue")
    a2 = ctl.generate([p2], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])

    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    b1 = eng.generate([p1], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])
    assert b1[0].token_ids == a1[0].token_ids
    st = eng.sessions
    with eng._paged_lock:
        with st.lock:
            got = st.alloc(st.n_pages - 1)
            assert got is not None
            st._release(got)
    assert st.get("s") is None and tier.has_session("s")
    # the hibernated entry carries its scale blocks
    entry = tier.host.sessions["s"]
    assert entry.k.dtype == np.int8 and entry.k_scale is not None
    b2 = eng.generate([p2], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])
    assert b2[0].token_ids == a2[0].token_ids
    assert tier.restored_sessions == 1


def test_disk_store_roundtrips_int8_scales(tmp_path):
    from quoracle_tpu.serving.kvtier import DiskPrefixStore
    s = DiskPrefixStore(str(tmp_path), "sig-q8", model="m")
    toks = list(range(128))
    rng = np.random.default_rng(3)
    k = rng.integers(-127, 128, (2, 128, 2, 16)).astype(np.int8)
    v = rng.integers(-127, 128, (2, 128, 2, 16)).astype(np.int8)
    ks = rng.random((2, 2, 128)).astype(np.float32)
    vs = rng.random((2, 2, 128)).astype(np.float32)
    key = s.block_key(toks)
    assert s.save(key, toks, k, v, ks, vs)
    loaded = s.load(key, toks)
    assert loaded is not None and len(loaded) == 4
    lk, lv, lks, lvs = loaded
    assert lk.dtype == np.int8
    assert lk.tobytes() == k.tobytes() and lv.tobytes() == v.tobytes()
    assert np.array_equal(lks, ks) and np.array_equal(lvs, vs)


def test_disk_store_rejects_flipped_scale_bytes(tmp_path):
    """A flipped byte in the APPENDED scale arrays is rejected by the
    same crc boundary as payload corruption — skip, unlink, never
    served."""
    from quoracle_tpu.serving.kvtier import DiskPrefixStore
    s = DiskPrefixStore(str(tmp_path), "sig-q8", model="m")
    toks = list(range(128))
    k = np.ones((2, 128, 2, 16), np.int8)
    ks = np.full((2, 2, 128), 0.5, np.float32)
    key = s.block_key(toks)
    assert s.save(key, toks, k, k, ks, ks)
    path = s._path(key)
    # flip a byte INSIDE the v_scale member's data (zipfile locates the
    # member; +256 clears the local header + npy header into raw f32s)
    import zipfile
    with zipfile.ZipFile(path) as zf:
        off = zf.getinfo("v_scale.npy").header_offset + 256
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]))
    assert s.load(key, toks) is None
    assert s.corrupt == 1
    assert not os.path.exists(path)       # unlinked, never served


def test_scale_corrupt_chaos_point(tmp_path):
    """The kvtier.scale_corrupt injection point flips a scale byte on
    the restore path and the crc boundary catches it end to end."""
    from quoracle_tpu.chaos.faults import CHAOS, FaultPlan, FaultRule
    from quoracle_tpu.serving.kvtier import DiskPrefixStore
    s = DiskPrefixStore(str(tmp_path), "sig-q8", model="m")
    toks = list(range(128))
    k = np.ones((2, 128, 2, 16), np.int8)
    ks = np.full((2, 2, 128), 0.25, np.float32)
    key = s.block_key(toks)
    assert s.save(key, toks, k, k, ks, ks)
    CHAOS.arm(FaultPlan(seed=3, rules=[
        FaultRule("kvtier.scale_corrupt", "corrupt")]))
    try:
        assert s.load(key, toks) is None
        assert s.corrupt == 1
    finally:
        CHAOS.disarm()


def test_envelope_roundtrips_int8_scales():
    from quoracle_tpu.serving.fabric import wire
    from quoracle_tpu.serving.handoff import HandoffEnvelope
    from quoracle_tpu.serving.kvtier import _HostSession
    rng = np.random.default_rng(4)
    k = rng.integers(-127, 128, (2, 3, 8, 2, 16)).astype(np.int8)
    v = rng.integers(-127, 128, (2, 3, 8, 2, 16)).astype(np.int8)
    ks = rng.random((2, 3, 2, 8)).astype(np.float32)
    vs = rng.random((2, 3, 2, 8)).astype(np.float32)
    entry = _HostSession([1, 2, 3, 4], 0, k, v, ks, vs)
    env = HandoffEnvelope(session_id="s", model_spec=MEMBER,
                          signature="sig-int8-q8kv", entry=entry,
                          json_state=5)
    blob = wire.encode_envelope(env)
    assert wire.peek_envelope(blob)["quant"] == "q8kv"
    out = wire.decode_envelope(blob, expect_signature="sig-int8-q8kv")
    e = out.entry
    assert e.k.dtype == np.int8
    assert e.k.tobytes() == k.tobytes() and e.v.tobytes() == v.tobytes()
    assert np.array_equal(e.k_scale, ks)
    assert np.array_equal(e.v_scale, vs)
    # truncated scale section → structured reject, never a partial adopt
    with pytest.raises(wire.WireError) as ei:
        wire.decode_envelope(blob[:-8])
    assert ei.value.reason == "truncated"
    # signature gate fires BEFORE any byte section parses
    with pytest.raises(wire.WireError) as ei:
        wire.decode_envelope(blob, expect_signature="sig-bfloat16")
    assert ei.value.reason == "signature"


def test_quantized_unquantized_peers_reject_handoff():
    """A quantized↔unquantized pair is a version-skewed pair: handoff
    rejects before bytes move; the request degrades to cold re-prefill
    (unit: adopt raises the structured reason)."""
    from quoracle_tpu.serving.handoff import HandoffError, KVHandoff
    tok = ByteTokenizer()
    p1 = enc(SYS + " task: say hi.")
    src = make_engine(quant=True)
    src.attach_tier(host_mb=64)
    src.generate([p1], temperature=0.0, max_new_tokens=4,
                 session_ids=["h"])
    h = KVHandoff()
    env = h.export(src, "h", MEMBER)
    dst = make_engine(quant=False)
    dst.attach_tier(host_mb=64)
    with pytest.raises(HandoffError) as ei:
        h.adopt(dst, env)
    assert ei.value.reason == "signature"
    assert h.rejects == 1
    # the historic (unquantized) signature is byte-identical to its
    # pre-ISSUE-13 form — existing disk stores stay warm
    assert dst.kv_signature() == (
        f"tiny-L{CFG.n_layers}x{CFG.n_kv_heads}x{CFG.head_dim}"
        f"-p{dst.sessions.page}-float32")
    assert src.kv_signature().endswith("-int8-q8kv")


def test_prefixd_roundtrips_int8_blobs(tmp_path):
    from quoracle_tpu.serving.fabric.prefixd import (
        PrefixdClient, PrefixService,
    )
    from quoracle_tpu.serving.fabric.transport import LoopbackTransport
    from quoracle_tpu.serving.kvtier import DiskPrefixStore
    svc = PrefixService(str(tmp_path))
    client = PrefixdClient(
        LoopbackTransport(svc.handle, "prefixd",
                          lock_name="fabric.prefixd"), "sig-int8-q8kv")
    tokens = list(range(128))
    key = DiskPrefixStore.block_key(tokens)
    k = np.full((2, 128, 2, 16), 7, np.int8)
    ks = np.full((2, 2, 128), 0.125, np.float32)
    assert client.publish(key, tokens, k, k, ks, ks)
    got = client.fetch(key, tokens)
    assert got is not None and len(got) == 4
    assert got[0].dtype == np.int8
    assert np.array_equal(got[2], ks)


# ---------------------------------------------------------------------------
# Capacity, planning, and observability
# ---------------------------------------------------------------------------

def test_resident_tokens_scale_with_byte_rate():
    # byte-bound session budget: the int8 pool holds more tokens at the
    # same bytes, by exactly the kv_token_bytes ratio
    budget = 1 << 20
    unq = GenerateEngine(CFG, PARAMS, ByteTokenizer(), max_seq=512,
                         prompt_buckets=(32, 64),
                         session_max_bytes=budget)
    qe = GenerateEngine(CFG, PARAMS, ByteTokenizer(), max_seq=512,
                        prompt_buckets=(32, 64),
                        session_max_bytes=budget, quantize_kv=True)
    rate_unq = kv_token_bytes(CFG.n_layers, CFG.n_kv_heads,
                              CFG.head_dim, 4, False)   # fp32 params
    rate_q = kv_token_bytes(CFG.n_layers, CFG.n_kv_heads,
                            CFG.head_dim, 1, True)
    assert qe.kv_token_pool_bytes() == rate_q < rate_unq
    assert qe.sessions.max_tokens > unq.sessions.max_tokens
    assert qe.quant_stats()["kv_compression"] > 1.0


def test_pool_sizing_quant_columns():
    from quoracle_tpu.parallel.mesh import pool_sizing
    base = pool_sizing([MEMBER], n_devices=1, host_kv_mb=256,
                       disk_kv_gb=1.0)
    quant = pool_sizing([MEMBER], n_devices=1, host_kv_mb=256,
                        disk_kv_gb=1.0, quantize_weights=True,
                        quantize_kv=True)
    mb, mq = base["members"][0], quant["members"][0]
    assert mb["weights_dtype"] == "bf16" and mb["kv_dtype"] == "bf16"
    assert mq["weights_dtype"] == "int8"
    assert mq["kv_dtype"] == "int8+scales"
    # resident/host/disk token figures ~double at the int8 rate
    assert mq["resident_kv_tokens"] > 1.5 * mb["resident_kv_tokens"]
    assert (mq["tiers"]["host_kv_tokens"]
            > 1.5 * mb["tiers"]["host_kv_tokens"])
    assert (mq["tiers"]["disk_kv_tokens"]
            > 1.5 * mb["tiers"]["disk_kv_tokens"])
    assert (mq["kv_bytes_per_token_per_chip"]
            < mb["kv_bytes_per_token_per_chip"])


def test_kv_stats_and_prometheus_exposition():
    from quoracle_tpu.infra.telemetry import METRICS
    from quoracle_tpu.models.runtime import TPUBackend
    b = TPUBackend([MEMBER], host_kv_mb=32, quantize_weights=True,
                   quantize_kv=True)
    try:
        r = b.query([req(sid="kv1", max_tokens=8)])[0]
        assert r.ok, r.error
        stats = b.kv_stats()
        q = stats["members"][MEMBER]["quant"]
        assert q["quantize_kv"] and q["quantize_weights"]
        assert q["kv_bytes_per_token"] < q["kv_bytes_per_token_bf16"]
        assert q["kv_compression"] > 1.0
        text = METRICS.render_prometheus()
        assert "quoracle_quant_kv_bytes_per_token" in text
        assert "quoracle_quant_bytes_saved_total" in text
        # the kv panel renders the compression column
        from quoracle_tpu.web.views import kv_panel
        html = kv_panel({"enabled": True, **stats})
        assert "compression" in html and "int8" in html
    finally:
        b.close()
