"""Does Mosaic accept the kernels? — without a chip.

The installed libtpu compiles for a TPU that is not there: a v5e topology
description stands in for the devices, and ``jit(f).lower(<shapes placed on
a topology device>).compile()`` runs the real Mosaic + XLA:TPU pipeline
under ``JAX_PLATFORMS=cpu``. Interpret-mode tests check what a kernel
computes; only this checks that it lowers — unsupported shape casts, block
shapes Pallas refuses, SMEM and VMEM budgets (PR 21 found five such
refusals, none visible from a CPU run).

Shapes: Mistral-7B's head geometry (32/8 heads, head_dim 128, 128-token
pages, 4096 window) at an 8k-token tick — 1024 blocks of 8 tokens, 8 row
slots, a 128-page table — the size at which a per-block copy of the page
table overflowed the v5e's 1 MiB of SMEM.

The second half runs the tp wrappers under ``jax.shard_map`` with the
kernels in interpret mode on two virtual devices: on CPU the dispatchers
normally pick the gather references, which hid a ``pallas_call`` that the
installed ``shard_map`` rejected at trace time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, SingleDeviceSharding

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.ops import flash_attention as fa
from quoracle_tpu.ops import paged_attention as pa

CFG = get_model_config("mistral-7b")
H, KV, HD, WINDOW = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim, \
    CFG.sliding_window
PAGE, N_PAGES = 128, 257


@pytest.fixture(scope="module")
def on_v5e():
    """ShapeDtypeStruct factory placing arrays on one v5e topology device."""
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def compiles(fn, *args) -> None:
    jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("tq,nb,rows", [(8, 1024, 8), (1, 8, 8)],
                         ids=["tq8-8k-tick", "tq1-decode"])
def test_ragged_kernel_compiles(on_v5e, tq, nb, rows, quant):
    S = on_v5e
    pool = S((N_PAGES, PAGE, KV, HD), jnp.int8 if quant else jnp.bfloat16)
    args = [S((nb * tq, H, HD), jnp.bfloat16), pool, pool,
            S((rows, 128), jnp.int32), S((4, nb), jnp.int32)]
    if quant:
        args += [S((N_PAGES, KV, PAGE), jnp.float32)] * 2

    def fn(q, k, v, tables, meta, ks=None, vs=None):
        return pa.ragged_attend(q, k, v, tables, meta, tq=tq,
                                sliding_window=WINDOW, k_scale=ks,
                                v_scale=vs)
    compiles(fn, *args)


def test_compiled_ragged_kernel_carries_its_pinned_name(on_v5e):
    """A profiler trace shows the kernel as ``%ragged_attend.<n>``, under
    the ``pallas_call``'s explicit ``name`` (ISSUE 24): the benchmark's
    metric files match on it, and the pools' re-layout for the kernel
    carries its own scope, ``kv_layout``."""
    import re
    S = on_v5e
    pool = S((N_PAGES, PAGE, KV, HD), jnp.bfloat16)
    text = jax.jit(functools.partial(
        pa.ragged_attend, tq=1, sliding_window=WINDOW)).lower(
        S((8, H, HD), jnp.bfloat16), pool, pool, S((8, 128), jnp.int32),
        S((4, 8), jnp.int32)).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1
    assert re.match(r"\s*%ragged_attend(\.\d+)? = ", call[0])
    assert "/ragged_attend/pallas_call" in call[0]
    assert "kv_layout/reshape" in text


def test_ragged_kernel_compiles_at_every_catalog_geometry(on_v5e):
    """The dispatcher routes every paged model to this kernel: all head
    geometries in the catalog must lower, prefill and decode."""
    S = on_v5e
    geometries = {(c.n_heads, c.n_kv_heads, c.head_dim, c.sliding_window)
                  for c in map(get_model_config,
                               ("llama-3-8b", "mistral-7b", "gemma-7b",
                                "llama-1b", "mistral-1b", "gemma-1b"))}
    for h, kv, hd, window in sorted(geometries, key=str):
        pool = S((N_PAGES, PAGE, kv, hd), jnp.bfloat16)
        for tq, nb in ((8, 64), (1, 8)):
            compiles(functools.partial(pa.ragged_attend, tq=tq,
                                       sliding_window=window),
                     S((nb * tq, h, hd), jnp.bfloat16), pool, pool,
                     S((8, 64), jnp.int32), S((4, nb), jnp.int32))


def test_paged_decode_kernel_compiles(on_v5e):
    S = on_v5e
    pool = S((N_PAGES, PAGE, KV, HD), jnp.bfloat16)
    compiles(functools.partial(pa.paged_attend, sliding_window=WINDOW),
             S((4, H, HD), jnp.bfloat16), pool, pool, S((4, 64), jnp.int32),
             S((4,), jnp.int32), S((4,), jnp.int32), S((4,), jnp.int32))


@pytest.mark.parametrize("window", [None, WINDOW])
def test_paged_prefill_kernel_compiles(on_v5e, window):
    """With a window (the [Tb, G] -> [Tb·G, 1] cast) and without (128
    queries of 4096 elements needed 18.8 MiB of the 16 MiB scoped VMEM)."""
    S = on_v5e
    pool = S((N_PAGES, PAGE, KV, HD), jnp.bfloat16)
    compiles(functools.partial(pa.paged_prefill_attend,
                               sliding_window=window),
             S((4, 256, H, HD), jnp.bfloat16), pool, pool,
             S((4, 64), jnp.int32), S((4,), jnp.int32))
    # the same budget at gemma-7b's 16 x 256
    gpool = S((N_PAGES, PAGE, 16, 256), jnp.bfloat16)
    compiles(functools.partial(pa.paged_prefill_attend,
                               sliding_window=window),
             S((4, 256, 16, 256), jnp.bfloat16), gpool, gpool,
             S((4, 64), jnp.int32), S((4,), jnp.int32))


@pytest.mark.parametrize("b,t,s", [(3, 512, 1024), (1, 1024, 32768 + 1024)],
                         ids=["B3", "32k-window"])
def test_flash_kernel_compiles(on_v5e, b, t, s):
    """B > 1 (the (1, tq) position block) and a cache as long as
    Mistral-7B's context (whole-S K/V blocks needed 32 MiB of VMEM)."""
    S = on_v5e
    compiles(functools.partial(fa.flash_attend, sliding_window=WINDOW),
             S((b, t, H, HD), jnp.bfloat16), S((b, s, KV, HD), jnp.bfloat16),
             S((b, s, KV, HD), jnp.bfloat16), S((b, t), jnp.int32),
             S((b,), jnp.int32))


# --- tp wrappers: shard_map around a pallas_call ----------------------------


@pytest.fixture(scope="module")
def tp_case(eight_devices):
    mesh = Mesh(np.array(eight_devices[:2]).reshape(1, 2), ("dp", "tp"))
    rng = np.random.default_rng(0)
    h, kv, hd, page, n_pages = 4, 2, 32, 8, 9

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {
        "mesh": mesh, "h": h, "kv": kv, "hd": hd,
        "kp": arr(n_pages, page, kv, hd), "vp": arr(n_pages, page, kv, hd),
        "tables": jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
        "pool_lens": jnp.asarray([20, 9], jnp.int32), "arr": arr,
    }


def test_ragged_tp_wrapper_runs_the_kernel_under_shard_map(tp_case):
    c = tp_case
    q = c["arr"](16, c["h"], c["hd"])
    meta = jnp.asarray([[20, 9], [12, 8], [8, 1], [0, 1]], jnp.int32)
    args = (q, c["kp"], c["vp"], c["tables"], meta)
    ref = pa.ragged_attend_ref(*args, tq=8)
    out = jax.jit(lambda *a: pa.ragged_attend_auto(
        *a, tq=8, interpret=True, shard=(c["mesh"], "tp")))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_split_tp_wrappers_run_the_kernels_under_shard_map(tp_case):
    c = tp_case
    shard = (c["mesh"], "tp", None)
    kv_off = jnp.zeros((2,), jnp.int32)
    # decode: pool piece (+) tail piece
    q = c["arr"](2, 1, c["h"], c["hd"])
    tail_k, tail_v = c["arr"](2, 4, c["kv"], c["hd"]), \
        c["arr"](2, 4, c["kv"], c["hd"])
    args = (q, c["kp"], c["vp"], c["tables"], c["pool_lens"], kv_off,
            tail_k, tail_v, jnp.asarray(2), c["pool_lens"] + 1)
    ref = pa.paged_decode_attend(*args)
    out = jax.jit(lambda *a: pa.paged_decode_attend(
        *a, interpret=True, shard=shard))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # prefill: pool prefix (+) intra-chunk piece
    q = c["arr"](2, 8, c["h"], c["hd"])
    ck, cv = c["arr"](2, 8, c["kv"], c["hd"]), c["arr"](2, 8, c["kv"],
                                                        c["hd"])
    args = (q, ck, cv, c["kp"], c["vp"], c["tables"], c["pool_lens"],
            jnp.asarray([8, 5], jnp.int32))
    ref = pa.paged_prefill_merge(*args)
    out = jax.jit(lambda *a: pa.paged_prefill_merge(
        *a, interpret=True, shard=shard))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_dispatcher_runs_the_kernel_under_shard_map(tp_case):
    """A mesh engine's long prefill chunk: GSPMD cannot partition a Mosaic
    kernel, so attend_auto lays it over the mesh itself."""
    from quoracle_tpu.ops.attention import attend
    c = tp_case
    q = c["arr"](2, 256, c["h"], c["hd"])
    k, v = c["arr"](2, 384, c["kv"], c["hd"]), c["arr"](2, 384, c["kv"],
                                                        c["hd"])
    q_pos = jnp.broadcast_to(128 + jnp.arange(256, dtype=jnp.int32), (2, 256))
    kv_len = jnp.asarray([384, 300], jnp.int32)
    ref = attend(q, k, v, q_pos, kv_len, sliding_window=100)
    out = jax.jit(lambda *a: fa.attend_auto(
        *a, sliding_window=100, interpret=True,
        shard=(c["mesh"], "tp", None)))(q, k, v, q_pos, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)
