"""Mamba-2 layers, attention with no positional embedding and ungated
experts, every layer one operator (Nemotron-H; ISSUE 47), at toy widths on
the CPU with seeded random weights and the published STRUCTURE (`MEMEM*E`
twice, 2 kv heads under 16 query heads each, groups of heads that share B
and C, 4 taps, 6 of 16 experts with half held, a scan chunk of 32 under
pages of 128): the program against the benchmark's plain reference
(`benchmark/families/mamba_moe.py`, written apart from it), on logits; the
chunked scan against the recurrence, alone; chunks cut anywhere; rows of
one tick apart; the two shares of the experts; and what the tolerance
tells apart. Through the engine: `tests/test_mamba_moe_engine.py`.

Tolerances. Program and reference are both float32 here and agree to about
3e-6 on logits of size 4 (the chunked scan sums a chunk's 32 decays in
another order than the recurrence): 2e-4 leaves room for that and is two
orders of magnitude below what a bfloat16 state (2e-2 and more below), a
missing gate of the norm or a missing square of the relu moves.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import mamba_moe as fam
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.ops import ssm_scan as sc

TOL = 2e-4
PAGE = 128

# the configuration file's keys at toy widths
RAW = dict(
    name="toy-nemotron", family="mamba_moe", model_type="nemotron_h",
    hybrid_override_pattern="MEMEM*EMEMEM*E", num_hidden_layers=14,
    hidden_size=64, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, conv_kernel=4, chunk_size=32, expand=2,
    num_attention_heads=32, num_key_value_heads=2, head_dim=8,
    n_routed_experts=8, held_experts_first=0, num_experts_per_tok=6,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_shared_experts=1, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, mlp_hidden_act="relu2", use_bias=False,
    mlp_bias=False, attention_bias=False, use_conv_bias=True,
    layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, vocab_size=512, tie_word_embeddings=False,
    torch_dtype="float32", eos_token_id=2, bos_token_id=1,
    reduced_from=dict(n_routed_experts=16),
    serving=dict(context_window=1024, output_limit=128, state_records=12))
SEED = 2 ** 31 + 47


def f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def model(raw):
    cfg = get_model_config(fam.register(raw))
    params = tr.init_params(cfg, jax.random.PRNGKey(SEED), dtype=jnp.bfloat16)
    return cfg, params, fam.Reference(raw, SEED)


@pytest.fixture(scope="module")
def toy():
    return model(RAW)


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


# -- the forward, called as the engine's programs call it -------------------

N_PAGES, N_REC = 33, 12


def new_pools(cfg):
    m = cfg.ssm
    kv = jnp.zeros((cfg.n_attn_layers, N_PAGES, PAGE, cfg.kv_pools[0]),
                   jnp.float32)
    n = cfg.n_ssm_layers * N_REC
    return kv, kv, (jnp.zeros((n, m.d_inner, m.state_dim), jnp.float32),
                    jnp.zeros((n, (m.conv_kernel - 1) * m.conv_dim),
                              jnp.float32))


@functools.partial(jax.jit, static_argnames=("cfg", "tq", "interpret"))
def _forward(params, cfg, toks, pos, kp, vp, tables, meta, dst, ssm, take,
             tq, interpret=None):
    out = tr.forward_hidden_ragged(params, cfg, toks[None], pos[None], kp,
                                   vp, tables, meta, dst, tq=tq, ssm=ssm,
                                   interpret=interpret)
    logits = tr.project_logits(params, cfg, out[0][0][take][None])[0]
    return logits, (out[1], out[2], out[6])


def tick(cfg, params, pools, rows, tq=8, interpret=None):
    """One ragged forward of `rows` = [(tokens, prefix already resident,
    record read or -1, record written[, (tokens into the chunk, record of
    a snapshot)])] (row r's pages: r*4 + 1 ..), laid out and described to
    the ssm layers as `GenerateEngine._run_unified` does it — by its own
    `_ssm_tick`; returns (logits [T, V] of the real tokens in order,
    pools)."""
    kp, vp, sp = pools
    toks, pos, dst, meta, take, segs, starts = [], [], [], [], [], [], []
    tables = np.zeros((8, 4), np.int32)
    last = np.zeros((8,), np.int32)
    for r, (t, pre, *_) in enumerate(rows):
        tables[r] = r * 4 + 1 + np.arange(4)
        nb = -(-len(t) // tq)
        base = len(toks)
        for b in range(nb):
            meta.append((pre + len(t), pre + b * tq,
                         min(tq, len(t) - b * tq), r))
        p = pre + np.arange(len(t))
        pad = nb * tq - len(t)
        toks += list(t) + [0] * pad
        pos += list(p) + [0] * pad
        dst += list(tables[r][p // PAGE] * PAGE + p % PAGE) \
            + [N_PAGES * PAGE] * pad
        take += list(range(base, base + len(t)))
        segs.append(len(t))
        starts.append(base)
        last[r] = base + len(t) - 1
    TB = -(-len(toks) // 16) * 16
    fill = TB - len(toks)
    toks, pos, dst = toks + [0] * fill, pos + [0] * fill, \
        dst + [N_PAGES * PAGE] * fill
    meta += [(0, 0, 0, 0)] * (TB // tq - len(meta))
    eng = types.SimpleNamespace(cfg=cfg, sessions=types.SimpleNamespace(
        page=PAGE, records=types.SimpleNamespace(n_ids=N_REC)))
    fields, _ = GenerateEngine._ssm_tick(
        eng, len(rows), 8, TB, segs, starts, last,
        (np.asarray([r[2] for r in rows], np.int32),
         np.asarray([r[3] for r in rows], np.int32),
         [r[4] if len(r) > 4 else (0, 0) for r in rows]))
    i32 = lambda a: jnp.asarray(np.asarray(a), jnp.int32)      # noqa: E731
    logits, pools = _forward(
        params, cfg, i32(toks), i32(pos), kp, vp, i32(tables),
        i32(np.asarray(meta).T), i32(dst), tr.SsmTick(*sp, *fields),
        i32(take), tq, interpret)
    return np.asarray(logits), pools


def want(ref, ids, upto=None):
    """The reference's logits at positions 0 .. upto of `ids` (one
    compiled length)."""
    n = len(ids) if upto is None else upto
    return ref.logits(np.pad(np.asarray(ids, np.int32), (0, 640 - len(ids))),
                      np.arange(n))


# -- both sides build the same model -----------------------------------------

def test_both_sides_draw_the_same_bits(toy):
    cfg, params, ref = toy
    assert cfg.layer_plan[1][1] == 2 and len(cfg.layer_plan[1][0]) == 7
    assert fam.plan(ref.s)[1] == (list("MEMEM*E"), 2)
    for mine, theirs in zip(params["segments"][1], ref.w["segments"][1]):
        for k, v in theirs.items():
            assert mine[k].dtype == v.dtype and mine[k].shape == v.shape, k
            assert bool(jnp.all(mine[k] == v)), k
        assert set(mine) - set(theirs) <= {"attn_norm", "mlp_norm",
                                           "ssm_norm"}
    # a step's decay is neither dead nor frozen at the drawn Δ
    p = params["segments"][1][0]
    decay = np.exp(-np.exp(np.asarray(p["a_log"])) * np.log1p(np.exp(
        np.asarray(p["dt_bias"]))))
    assert 0.19 < decay.min() and decay.max() < 0.9991


def test_one_statement_of_what_a_session_holds(toy):
    cfg = toy[0]
    m = cfg.ssm
    assert cfg.state_record == ((8 * 8 * 16, "float32"),
                                (3 * (64 + 2 * 2 * 16), None))
    assert cfg.n_ssm_layers == 6 and cfg.n_attn_layers == 2
    assert cfg.state_bytes_per_record(4) == fam.stated_precision(RAW)[
        "state_bytes_per_record"] == 6 * (1024 + 3 * 128) * 4
    assert cfg.kv_bytes_per_token(dtype_bytes=4) == fam.stated_precision(
        RAW)["kv_bytes_per_token"] == 2 * 2 * 2 * 8 * 4
    assert m.in_dim == 64 + 128 + 8 and not cfg.plain
    assert cfg.n_params == tr.param_count(tr.init_params(
        cfg, jax.random.PRNGKey(0)))


def test_the_count_at_the_published_widths():
    """ISSUE 47's reckoning of the cut: 4,584,903,936 parameters, 2,048
    bytes a resident token, 12,804,096 bytes a record."""
    from benchmark import configs
    raw = configs.load_config("nemotron-3-nano-30b-a3b-ep2-l14")
    cfg = get_model_config(fam.register(raw))
    assert cfg.n_params == 4_584_903_936
    assert cfg._layer_params(None, "ssm") == 38_744_896
    assert cfg._layer_params(None, "attention") == 23_399_040
    assert cfg._layer_params("experts", None) == 658_885_376
    assert fam.stated_precision(raw) == {
        "kv_bytes_per_token": 2048, "state_bytes_per_record": 12_804_096}
    assert cfg.kv_bytes_per_token() == 2048
    assert cfg.state_bytes_per_record() == 12_804_096
    assert cfg.layer_plan[1] == ((("ssm", None), (None, "experts")) * 2
                                 + (("ssm", None), ("attention", None),
                                    (None, "experts")), 2)


# -- the chunked scan against the recurrence, alone --------------------------

def scan_case(seed, dtype=jnp.float32):
    """Ragged rows with unequal starts in the scan layout: 3 rows of 3, 1
    and 2 chunks of 16, the first row's last chunk padded, each row from
    a state of its own."""
    rng = np.random.default_rng(seed)
    NC, Q, H, P, G, N, R = 6, 16, 4, 8, 2, 16, 3
    x = jnp.asarray(rng.normal(size=(NC, Q, H, P)), dtype)
    dt = np.log1p(np.exp(rng.normal(size=(NC, Q, H)) - 1)).astype(np.float32)
    dt[2, 9:] = 0.0
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(NC, Q, G, N)), dtype)
    C = jnp.asarray(rng.normal(size=(NC, Q, G, N)), dtype)
    s0 = jnp.asarray(rng.normal(size=(R, H, P, N)), jnp.float32)
    row = jnp.asarray([0, 0, 0, 1, 2, 2])
    first = jnp.asarray([1, 0, 0, 1, 1, 0])
    return x, jnp.asarray(dt), A, B, C, s0, row, first


def ssm_step(x, dt, A, B, C, S):
    """The recurrence as it is written, one token a row: ``x [R, H, P]``; ``dt [R, H]`` float32; ``A [H]``;
    ``B``, ``C [R, G, N]``; ``S [R, H, P, N]`` float32. Returns (``y [R,
    H, P]`` float32, the new ``S``)."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    f32 = jnp.float32
    Sg = S.reshape(R, G, H // G, P, N)
    xd = (x.astype(f32) * dt[..., None]).reshape(R, G, H // G, P)
    Sg = jnp.exp(dt * A).reshape(R, G, H // G, 1, 1) * Sg \
        + xd[..., None] * B.astype(f32)[:, :, None, None, :]
    y = (Sg * C.astype(f32)[:, :, None, None, :]).sum(-1)
    return y.reshape(R, H, P), Sg.reshape(R, H, P, N)


def recurrence(x, dt, A, B, C, s0, row, first):
    """`ssm_step`, a token at a time over the scan layout."""
    NC, Q = x.shape[:2]
    ys, ends, S = [], [], None
    for c in range(NC):
        if int(first[c]):
            S = s0[int(row[c])][None]
        for t in range(Q):
            y, S = ssm_step(x[c, t][None], dt[c, t][None], A,
                               B[c, t][None], C[c, t][None], S)
            ys.append(y[0])
        ends.append(S[0])
    return (jnp.stack(ys).reshape(NC, Q, *x.shape[2:]), jnp.stack(ends))


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_chunked_scan_is_the_recurrence(form):
    args = scan_case(5)
    y, ends = recurrence(*args)
    got = sc.ssm_scan_ref(*args) if form == "xla" \
        else sc.ssm_scan(*args, interpret=True)
    assert np.abs(np.asarray(got[0] - y)).max() < 2e-5
    assert np.abs(np.asarray(got[1] - ends)).max() < 2e-5
    if form == "kernel":
        # the layout's unused chunks are skipped: told that five chunks
        # hold tokens, the kernel gives the first five as before
        some = sc.ssm_scan(*args, jnp.asarray([5], jnp.int32),
                           interpret=True)
        assert np.array_equal(np.asarray(some[0][:5]), np.asarray(got[0][:5]))
        assert np.array_equal(np.asarray(some[1][:5]), np.asarray(got[1][:5]))
    # padding (Δ = 0) leaves the state as it is: row 0's end state is the
    # state after its last real token
    assert np.abs(np.asarray(ends[2] - recurrence(
        *(a[:3] if i < 5 and i != 2 else a for i, a in enumerate(args[:5])),
        args[5], args[6][:3], args[7][:3])[1][2])).max() < 1e-6


def test_the_kernel_takes_bfloat16_activations():
    args = scan_case(7, jnp.bfloat16)
    y, ends = sc.ssm_scan_ref(*args)
    got = sc.ssm_scan(*args, interpret=True)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    assert np.abs(np.asarray(got[0].astype(jnp.float32) - y)).max() < 0.25
    assert np.abs(np.asarray(got[1] - ends)).max() < 0.1


# -- chunk forward, then decode, through the record pool ----------------------

@pytest.mark.parametrize("n", [45, 64, 200, 256],
                         ids=["inside-a-chunk", "two-chunks-whole",
                              "past-a-page", "two-pages-whole"])
def test_reference_agrees_with_the_ragged_forward(toy, n):
    cfg, params, ref = toy
    ids = tokens_of(n, n)
    got, _ = tick(cfg, f32(params), new_pools(cfg), [(ids, 0, -1, 1)])
    assert np.abs(got - want(ref, ids)).max() < TOL


@pytest.mark.parametrize("cuts,fused", [
    ((45, 64, 65, 130), False), ((128, 256), False), ((1, 32, 33), False),
    ((45, 64, 65, 130), True)],
    ids=["anywhere", "on-pages", "one-token-first", "decode-kernel"])
def test_a_prompt_may_span_ticks_and_then_decode(toy, cuts, fused):
    """Several chunk forwards that go on from the row's own record, then
    one-token ticks (the decode program's forward, `past` None): the
    logits at every position are the reference's — with the decode step in
    plain XLA (`ssm_decode_ref`), and with its convolution, recurrence
    and norm in the one kernel the TPU runs (`ssm_decode`, interpreted)."""
    cfg, params, ref = toy
    ids = tokens_of(11, 300)
    pools, got, pre = new_pools(cfg), [], 0
    for cut in (*cuts, 290):
        part, pools = tick(cfg, f32(params), pools,
                           [(ids[pre:cut], pre, 1 if pre else -1, 1)])
        got.append(part)
        pre = cut
    # decode steps, tq = 1, on the loop's own buffers: row 0's record read
    # once, every step updating it there; they lie as the decode kernel
    # takes them, the state transposed and the conv inputs float32
    kp, vp, sp = pools
    local = tuple(jnp.stack([
        jnp.zeros((8, *pool.shape[1:]), pool.dtype).at[0].set(
            pool[c * N_REC + 1]) for c in range(cfg.n_ssm_layers)])
        for pool in sp)
    local = (local[0].transpose(0, 1, 3, 2), local[1].astype(jnp.float32))
    live = np.zeros((8,), np.int32)
    live[0] = 1
    for t in range(290, 300):
        tables = np.zeros((8, 4), np.int32)
        tables[0] = 1 + np.arange(4)
        meta = np.zeros((4, 8), np.int32)
        meta[:, 0] = (t + 1, t, 1, 0)
        dst = np.full((8,), N_PAGES * PAGE, np.int32)
        dst[0] = tables[0][t // PAGE] * PAGE + t % PAGE
        i32 = lambda a: jnp.asarray(a, jnp.int32)           # noqa: E731
        toks = np.zeros((8,), np.int32)
        toks[0] = ids[t]
        logits, (kp, vp, local) = _forward(
            f32(params), cfg, i32(toks), i32(np.full((8,), t)), kp, vp,
            i32(tables), i32(meta), i32(dst),
            tr.SsmTick(*local, None, i32(live)), i32([0]), 1,
            True if fused else None)
        got.append(np.asarray(logits))
    assert np.abs(np.concatenate(got) - want(ref, ids)).max() < TOL


def test_rows_of_one_tick_start_from_their_own_records(toy):
    """Three rows in one forward — a new one, one going on from its
    record, one starting from ANOTHER record (a snapshot's: adoption is
    this copy) — and the snapshot a row takes at a page's end is the state
    a later row starts from."""
    cfg, params, ref = toy
    a, b = tokens_of(21, 300), tokens_of(22, 200)
    pools = new_pools(cfg)
    # a's first 256 tokens; a snapshot after its first page into record 5
    _, pools = tick(cfg, f32(params), pools,
                    [(a[:256], 0, -1, 1, (128, 5))])
    rows = [(a[256:], 256, 1, 1), (b, 0, -1, 2)]
    got, pools = tick(cfg, f32(params), pools, rows)
    assert np.abs(got[:44] - want(ref, a)[256:]).max() < TOL
    assert np.abs(got[44:] - want(ref, b)).max() < TOL
    # a second sequence shares a's first page: row 2 holds a copy of its
    # K/V pages (here: a's own table, read in place) and starts from the
    # snapshot, writing a record of its own
    c = np.concatenate([a[:128], tokens_of(23, 60)])
    kp, vp, sp = pools
    page = lambda pool: pool.at[:, 9].set(pool[:, 1])       # noqa: E731
    got, pools = tick(cfg, f32(params), (page(kp), page(vp), sp),
                      [(b[:8], 0, -1, 3), (b[:8], 0, -1, 4),
                       (c[128:], 128, 5, 6)])
    assert np.abs(got[16:] - want(ref, c)[128:]).max() < TOL
    # the snapshot stands as it was, and the first session's record too
    again, _ = tick(cfg, f32(params), (page(kp), page(vp), pools[2]),
                    [(b[:8], 0, -1, 3), (b[:8], 0, -1, 4),
                     (c[128:], 128, 5, 7)])
    assert np.array_equal(again[16:], got[16:])


def _scans(jaxpr):
    """Every `scan` in a jaxpr, however deep: (its body's size, its
    length, its `unroll`)."""
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        for sub in subs:
            yield from _scans(getattr(sub, "jaxpr", sub))
        if eqn.primitive.name == "scan":
            yield (len(eqn.params["jaxpr"].jaxpr.eqns),
                   eqn.params["length"], eqn.params["unroll"])


def test_a_decode_step_holds_both_repeats_of_the_period(toy):
    """The one-token forward — the decode loop's body — holds every repeat
    of the period in the scan's body (no loop inside the decode loop:
    fewer device operations a step), the chunk forward keeps the loop and
    a program of the period's size. What both compute is the reference's
    (the tests above)."""
    cfg, params, _ = toy
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    kp, vp, sp = new_pools(cfg)
    fields, _ = GenerateEngine._ssm_tick(
        types.SimpleNamespace(cfg=cfg, sessions=types.SimpleNamespace(
            page=PAGE, records=types.SimpleNamespace(n_ids=N_REC))),
        1, 8, 64, [40], [0], np.zeros((8,), np.int32),
        (np.full((1,), -1, np.int32), np.ones((1,), np.int32), [(0, 0)]))
    local = (jnp.zeros((cfg.n_ssm_layers, 8, cfg.ssm.state_dim,
                        cfg.ssm.d_inner)),
             jnp.zeros((cfg.n_ssm_layers, 8, sp[1].shape[1])))

    def period_scan(n_tok, tq, ssm):
        jaxpr = jax.make_jaxpr(
            lambda *a: tr.forward_hidden_ragged(
                f32(params), cfg, a[0][None], a[1][None], kp, vp, a[2], a[3],
                a[4], tq=tq, ssm=ssm))(
            i32(n_tok), i32(n_tok), i32(8, 4), i32(4, n_tok // tq),
            i32(n_tok))
        return max(_scans(jaxpr.jaxpr))     # the layers' is the largest

    _, length, unroll = period_scan(64, 8, tr.SsmTick(*sp, *(
        jnp.asarray(f) for f in fields)))
    assert (length, unroll) == (2, 1)
    _, length, unroll = period_scan(8, 1, tr.SsmTick(
        *local, None, jnp.ones((8,), jnp.int32)))
    assert (length, unroll) == (2, 2)


def test_the_forward_with_its_kernels_is_the_forward_without(toy):
    """Interpreted, the three kernels a tick of this model runs — the
    attention tile kernel at 16 query heads a kv head, the grouped
    UNGATED experts, the scan — give the XLA forms' logits."""
    cfg, params, _ = toy
    a, b = tokens_of(31, 150), tokens_of(32, 40)
    rows = [(a, 0, -1, 1), (b, 0, -1, 2)]
    plain, _ = tick(cfg, f32(params), new_pools(cfg), rows)
    kern, _ = tick(cfg, f32(params), new_pools(cfg), rows, interpret=True)
    assert np.abs(kern - plain).max() < TOL


# -- the expert layer ----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(toy):
    """Experts 0-7 and 8-15 as two shares, the shared expert (a width of
    its own, which every share computes) counted once, are the layer with
    all 16 held; and the body is ungated: two matrices, the square of the
    relu."""
    cfg, _, _ = toy
    m = cfg.moe
    whole = dataclasses.replace(cfg, name="toy-nemotron-whole",
                                moe=dataclasses.replace(m, n_held=16))
    pw = f32(tr.init_params(whole, jax.random.PRNGKey(SEED)))
    layer = pw["segments"][1][1]
    assert set(layer) == {"mlp_norm", "router", "router_bias", "we_up",
                          "we_down", "ws_up", "ws_down"}
    assert layer["we_up"].shape == (2, 16, 32, 64) == layer["we_down"].shape
    assert layer["ws_up"].shape == (2, 48, 64)
    p = {k: v[1] for k, v in layer.items() if not k.startswith("we_")}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64), jnp.float32)
    valid = jnp.ones((40,), bool)
    experts = (layer["we_up"], layer["we_down"])
    with jax.default_matmul_precision("highest"):
        uncut, _ = tr._moe(x, p, experts, 1, whole, valid)
        h = tr.rmsnorm(x, p["mlp_norm"], whole.norm_eps, False)[0]
        shared = jnp.square(jax.nn.relu(h @ p["ws_up"].T)) @ p["ws_down"]
        total = jnp.zeros_like(x)
        for share in range(2):
            part = dataclasses.replace(
                cfg, name=f"toy-nemotron-{share}",
                moe=dataclasses.replace(m, held_start=8 * share))
            held = tuple(w[:, 8 * share:8 * share + 8] for w in experts)
            y, stats = tr._moe(x, p, held, 1, part, valid)
            total = total + (y - x) - shared[None]
            assert int(stats[0]) == 40 * 6
    assert np.abs(np.asarray(shared)).max() > 0.1
    assert np.abs(np.asarray(total + shared[None] + x - uncut)).max() < 1e-5


# -- what the tolerance tells apart --------------------------------------------

def test_the_tolerance_tells_a_broken_layer_from_the_sound_one(toy,
                                                               monkeypatch):
    """The sound program passes TOL against the reference; the reference
    with its state rounded to bfloat16 after every token, with the norm's
    gate left out, or with the relu not squared each lies far outside it
    — the tolerance sees a state kept in the wrong type and either
    missing piece."""
    cfg, params, ref = toy
    ids = tokens_of(41, 200)
    got, _ = tick(cfg, f32(params), new_pools(cfg), [(ids, 0, -1, 1)])
    sound = want(ref, ids)
    assert np.abs(got - sound).max() < TOL

    def broken(name, fn):
        monkeypatch.setattr(fam, name, fn)
        try:
            return np.abs(want(fam.Reference(RAW, SEED), ids) - got).max()
        finally:
            monkeypatch.undo()

    mamba = fam._mamba
    assert broken("_mamba", lambda s, w, x, r, z: mamba(
        s, w, x, r, z, state_dtype=jnp.bfloat16)) > 100 * TOL
    assert broken("_gate", lambda y, z: y) > 100 * TOL
    assert broken("_relu2", jax.nn.relu) > 100 * TOL
