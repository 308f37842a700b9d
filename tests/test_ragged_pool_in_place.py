"""The pool in place (ISSUE 25): the ragged forward and the decode loop
carry the whole stored pool; a plain per-layer reference does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.generate import decode_ragged
from quoracle_tpu.models.quant import kv_dequant, kv_quant
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.ops import paged_attention as pa

# --- the pool in place (ISSUE 25): a plain per-layer reference --------------
#
# forward_hidden_ragged carries the WHOLE pool, stored [L, n_pages, page,
# KV·hd], through its layer scan and writes each layer's fresh rows at a
# whole-pool index; decode_ragged carries it through its loop. The
# reference below does none of that: one row at a time, one layer at a
# time, a pool it indexes as [layer][page][slot][kv-head], dense attention
# over the row's own tokens.

PG = 8          # tokens a page in these tests


def _tiny(n_layers=2, n_kv_heads=2, window=None):
    return ModelConfig(name=f"pool-l{n_layers}-kv{n_kv_heads}-w{window}",
                       vocab_size=97, dim=32, n_layers=n_layers, n_heads=4,
                       n_kv_heads=n_kv_heads, ffn_dim=64, head_dim=16,
                       sliding_window=window)


class _PlainPool:
    """[L, n_pages, PG, KV, hd] float32, or int8 with scales
    [L, n_pages, KV, PG]: the 5-D view of what the engine stores."""

    def __init__(self, cfg, n_pages, rng, quant):
        shape = (cfg.n_layers, n_pages, PG, cfg.n_kv_heads, cfg.head_dim)
        self.quant = quant
        if quant:
            self.k, self.ks = map(np.array, kv_quant(
                jnp.asarray(rng.standard_normal(shape), jnp.float32)))
            self.v, self.vs = map(np.array, kv_quant(
                jnp.asarray(rng.standard_normal(shape), jnp.float32)))
            self.ks = self.ks.transpose(0, 1, 3, 2).copy()
            self.vs = self.vs.transpose(0, 1, 3, 2).copy()
        else:
            self.k = rng.standard_normal(shape).astype(np.float32)
            self.v = rng.standard_normal(shape).astype(np.float32)
            self.ks = self.vs = None

    def stored(self):
        """The engine's arrays: (k, v, k_scale, v_scale), lane-flat."""
        flat = self.k.shape[:3] + (-1,)
        s = (None, None) if not self.quant else (jnp.asarray(self.ks),
                                                 jnp.asarray(self.vs))
        return (jnp.asarray(self.k.reshape(flat)),
                jnp.asarray(self.v.reshape(flat))) + s

    def write(self, layer, page, slot, k, v):
        """One token's K and V ([KV, hd]) into its slot."""
        for pool, scales, x in ((self.k, self.ks, k), (self.v, self.vs, v)):
            if self.quant:
                q, s = kv_quant(x)
                pool[layer, page, slot] = np.asarray(q)
                scales[layer, page, :, slot] = np.asarray(s)
            else:
                pool[layer, page, slot] = np.asarray(x)

    def read(self, layer, pages):
        """A row's K and V over its page list: [len(pages)·PG, KV, hd]."""
        out = []
        for pool, scales in ((self.k, self.ks), (self.v, self.vs)):
            x = pool[layer, pages]                    # [n, PG, KV, hd]
            if self.quant:
                x = np.asarray(kv_dequant(
                    jnp.asarray(x),
                    jnp.asarray(scales[layer, pages].transpose(0, 2, 1))))
            out.append(x.reshape(-1, *x.shape[2:]).astype(np.float32))
        return out


def _plain_row_forward(params, cfg, pool, table, start, tokens):
    """One row's ``tokens`` at buffer positions start.. through every
    layer against ``pool`` (written as it goes); the last token's logits."""
    T = len(tokens)
    pos = start + np.arange(T)
    positions = jnp.asarray(pos, jnp.int32)[None]
    x = tr._embed(params, cfg, jnp.asarray(tokens, jnp.int32)[None])
    G = cfg.n_heads // cfg.n_kv_heads
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k, v = tr._qkv(x, p, cfg, 1, T, positions)
        for t in range(T):
            pool.write(layer, table[pos[t] // PG], pos[t] % PG,
                       k[0, t], v[0, t])
        ks, vs = pool.read(layer, table)
        s_idx = np.arange(ks.shape[0])
        mask = s_idx[None, :] <= pos[:, None]
        if cfg.sliding_window is not None:
            mask &= pos[:, None] - s_idx[None, :] < cfg.sliding_window
        qf = np.asarray(q[0], np.float32) * cfg.head_dim ** -0.5
        scores = np.einsum("thd,shd->hts", qf, np.repeat(ks, G, axis=1))
        scores = np.where(mask[None], scores, -1e30)
        prob = np.exp(scores - scores.max(-1, keepdims=True))
        prob = np.where(mask[None], prob, 0.0)
        prob /= prob.sum(-1, keepdims=True)
        attn = np.einsum("hts,shd->thd", prob, np.repeat(vs, G, axis=1))
        x = tr._attn_out(x, jnp.asarray(attn, x.dtype)[None], p, cfg)
        x = tr._mlp(x, p, cfg)
    hidden = tr._final_norm(x, params, cfg)
    return np.asarray(tr.project_logits(params, cfg, hidden[:, -1:])[0, 0])


def _flat_tick(rows, tq, n_tok):
    """The token-major layout ``_run_unified`` builds, for rows of
    (table, resident tokens, chunk tokens)."""
    NB = sum(-(-len(c) // tq) for _, _, c in rows)
    Tp, maxp = NB * tq, max(len(t) for t, _, _ in rows)
    tok = np.zeros((Tp,), np.int32)
    posn = np.zeros((Tp,), np.int32)
    dst = np.full((Tp,), n_tok, np.int32)        # padding slots drop
    meta = np.zeros((4, NB), np.int32)
    tables = np.zeros((len(rows), maxp), np.int32)
    last = np.zeros((len(rows),), np.int32)
    cur = 0
    for r, (table, pre, chunk) in enumerate(rows):
        s = len(chunk)
        nb = -(-s // tq)
        p = pre + np.arange(s)
        tok[cur:cur + s] = chunk
        posn[cur:cur + s] = p
        dst[cur:cur + s] = np.asarray(table)[p // PG] * PG + p % PG
        blk = cur // tq + np.arange(nb)
        meta[0, blk], meta[3, blk] = pre + s, r
        meta[1, blk] = pre + np.arange(nb) * tq
        meta[2, blk] = np.minimum(tq, s - np.arange(nb) * tq)
        tables[r, :len(table)] = table
        last[r] = cur + s - 1
        cur += nb * tq
    lens = np.asarray([pre + len(c) for _, pre, c in rows], np.int32)
    return tok, posn, dst, meta, tables, last, lens


def _stored_view(arrs, cfg):
    """(k, v[, k_scale, v_scale]) as stored → numpy, K and V 5-D."""
    k, v, *scales = arrs
    five = k.shape[:3] + (cfg.n_kv_heads, cfg.head_dim)
    return [np.asarray(k).reshape(five), np.asarray(v).reshape(five)] \
        + [np.asarray(s) for s in scales if s is not None]


def _assert_pool(got, pool, quant):
    want = [pool.k, pool.v] + ([pool.ks, pool.vs] if quant else [])
    for g, w in zip(got, want, strict=True):
        if quant and g.dtype == np.int8:
            # a value on a rounding edge may land one level apart between
            # the flattened batch's matmul and the row's own
            assert np.abs(g.astype(np.int32) - w).max() <= 1
            assert (g != w).mean() < 1e-3
        else:
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("interpret", [None, True, "tiles"],
                         ids=["xla", "kernel", "tile-kernel"])
@pytest.mark.parametrize("case", [
    dict(), dict(window=6), dict(n_kv_heads=4), dict(quant=True),
    dict(share=True),
], ids=["kv2", "kv2-window6", "kv4", "kv2-int8", "kv2-shared-walk"])
def test_pool_in_place_matches_plain_per_layer_reference(case, interpret):
    """``forward_hidden_ragged`` then ``decode_ragged`` — the pool a scan
    carry, then a loop carry, written at whole-pool indices and read by
    layer index — against the plain reference: the same greedy tokens and
    the same pool, every page of every layer. ``tile-kernel``: the chunk
    forward's attention walks two blocks a tile (the engine's call).
    ``shared-walk``: two of the rows begin with the same pages, and the
    decode loop is told so (``shared_walks``), as the engine tells it."""
    from quoracle_tpu.ops.paged_attention import ragged_tiles, shared_walks
    case_tiles, tiled = interpret == "tiles", {}
    interpret = None if interpret is None else True
    quant = case.get("quant", False)
    cfg = _tiny(3, case.get("n_kv_heads", 2), case.get("window"))
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    n_pages, tq, max_new = 9, 4, 5
    pool = _PlainPool(cfg, n_pages, rng, quant)
    stored = pool.stored()
    # (page table, resident tokens, chunk): a fresh row, a resumed row
    # whose chunk crosses a page, a one-token continuation
    rows = [([3, 7], 0, rng.integers(1, 97, 7)),
            ([5, 1, 8], 9, rng.integers(1, 97, 6)),
            ([2, 6], 4, rng.integers(1, 97, 1))]
    shared = None
    if case.get("share"):
        # rows 0 and 1 resume behind the same resident pages, row 1 from
        # their very end; the pool is larger by those pages
        common = list(range(9, 9 + pa.SHARED_MIN_PAGES))
        n_pages, held = 9 + len(common), len(common) * PG
        rows = [(common + [3, 7], held + 2, rng.integers(1, 97, 3)),
                (common + [5, 1], held, rng.integers(1, 97, 6)),
                ([2, 6], 4, rng.integers(1, 97, 1))]
        pool = _PlainPool(cfg, n_pages, rng, quant)
        stored = pool.stored()
    tok, posn, dst, meta, tables, last, lens0 = _flat_tick(
        rows, tq, n_pages * PG)
    if case.get("share"):
        shared = shared_walks(tables, lens0, PG)
        assert shared[0].tolist() == [len(common), len(common), 0]
        shared = jnp.asarray(shared)
    if case_tiles:
        tiled = dict(tile=2 * tq,
                     tiles=jnp.asarray(ragged_tiles(meta, tq, 2 * tq)))

    @jax.jit
    def tick(k, v, ks, vs):
        out = tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(tok)[None], jnp.asarray(posn)[None],
            k, v, jnp.asarray(tables), jnp.asarray(meta), jnp.asarray(dst),
            tq=tq, interpret=interpret, k_scale=ks, v_scale=vs, **tiled)
        hidden, pools = out[0], out[1:]      # (k, v, k_scale, v_scale)
        first = tr.project_logits(params, cfg,
                                  hidden[0][last][:, None])[:, 0]
        R = len(rows)
        res = decode_ragged(
            params, cfg, pools[0], pools[1], jnp.asarray(tables),
            jnp.asarray(lens0), jnp.zeros((R,), jnp.int32),
            first, jax.random.PRNGKey(0), jnp.zeros((R,)), jnp.ones((R,)),
            max_new, -1, active=jnp.ones((R,), bool),
            row_limit=jnp.full((R,), max_new, jnp.int32),
            interpret=interpret, k_scale=pools[2], v_scale=pools[3],
            shared=shared)
        return res[0], res[1], res[2], res[3:7]

    out, n_emitted, lens, pools_out = tick(*stored)

    want = []
    for table, pre, chunk in rows:
        logits = _plain_row_forward(params, cfg, pool, table, pre,
                                    list(chunk))
        toks, at = [int(logits.argmax())], pre + len(chunk)
        for _ in range(max_new - 1):
            logits = _plain_row_forward(params, cfg, pool, table, at,
                                        [toks[-1]])
            toks.append(int(logits.argmax()))
            at += 1
        want.append(toks)
    assert np.asarray(out).tolist() == want
    assert np.asarray(n_emitted).tolist() == [max_new] * 3
    assert np.asarray(lens).tolist() == [
        pre + len(c) + max_new - 1 for _, pre, c in rows]
    _assert_pool(_stored_view(pools_out, cfg), pool, quant)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("n_layers", [1, 2, 3])
def test_dropped_writes_stay_dropped_in_every_layer(n_layers, interpret):
    """The sentinel trap: a dropped write carries the index n_tok — out of
    range of ONE layer's pages, and the first slot of the NEXT layer's
    once the layer offset is added. A tick with padding slots, then a
    decode with a done row and a row at its page table's edge, must leave
    every page it does not own bit-identical in every layer — scratch
    page 0 included, where layer l's dropped write would land in layer
    l+1."""
    cfg = _tiny(n_layers)
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    rng = np.random.default_rng(11)
    n_pages, tq, max_new = 8, 4, 4
    pool = _PlainPool(cfg, n_pages, rng, False)
    k0, v0, _, _ = pool.stored()
    # row 0 writes (pages 3 and 5); row 1 will be done from the start
    # (page 6); row 2 fills its one-page table to the edge (page 2)
    rows = [([3, 5], 5, rng.integers(1, 97, 2)),
            ([6, 0], 2, rng.integers(1, 97, 1)),
            ([2, 0], PG - 3, rng.integers(1, 97, 3))]
    tok, posn, dst, meta, tables0, last, lens0 = _flat_tick(
        rows, tq, n_pages * PG)
    assert (dst == n_pages * PG).sum() == 6        # the padding slots

    @jax.jit
    def tick(k, v):
        hidden, k, v, *_ = tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(tok)[None], jnp.asarray(posn)[None],
            k, v, jnp.asarray(tables0), jnp.asarray(meta),
            jnp.asarray(dst), tq=tq, interpret=interpret)
        first = tr.project_logits(params, cfg,
                                  hidden[0][last][:, None])[:, 0]
        # decode: row 0 runs on (table [3, 5]); row 1 inactive; row 2's
        # table is its one full page, so lens // page >= maxp at once
        outs = []
        for tbl, act in ((tables0, [True, False, False]),
                         (np.asarray([[2], [2], [2]], np.int32),
                          [False, False, True])):
            res = decode_ragged(
                params, cfg, k, v, jnp.asarray(tbl),
                jnp.asarray(lens0), jnp.zeros((3,), jnp.int32),
                first, jax.random.PRNGKey(0), jnp.zeros((3,)),
                jnp.ones((3,)), max_new, -1, active=jnp.asarray(act),
                row_limit=jnp.full((3,), max_new, jnp.int32),
                interpret=interpret)
            outs.append(res[1])
            k, v = res[3], res[4]
        return k, v, outs

    k1, v1, emitted = tick(k0, v0)
    assert np.asarray(emitted[0]).tolist() == [max_new, 0, 0]
    assert np.asarray(emitted[1]).tolist() == [0, 0, max_new]
    five = (n_layers, n_pages, PG, cfg.n_kv_heads, cfg.head_dim)
    # what the tick owns: row 0's slots 5..6 + its 3 decode steps (7..9),
    # row 1's slot 2, row 2's slots PG-3..PG-1 — in every layer
    owned = np.zeros((n_pages, PG), bool)
    owned[3, 5:8] = owned[5, 0:2] = True
    owned[6, 2] = True
    owned[2, PG - 3:] = True
    for before, after in ((k0, k1), (v0, v1)):
        before = np.asarray(before).reshape(five)
        after = np.asarray(after).reshape(five)
        changed = (before != after).any(axis=(3, 4))     # [L, pages, PG]
        for layer in range(n_layers):
            assert (changed[layer] & ~owned).sum() == 0, (
                layer, np.argwhere(changed[layer] & ~owned))
            assert changed[layer][owned].all(), layer
