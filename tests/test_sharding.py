"""Mesh/sharding: tp×dp specs produce identical results to single-device."""

import time
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import forward, init_cache, init_params
from quoracle_tpu.parallel.mesh import (
    cache_spec, data_spec, make_mesh, param_specs, shard_params,
)


def test_make_mesh_shapes(eight_devices):
    mesh = make_mesh(n_devices=8, tp=4)
    assert dict(mesh.shape) == {"dp": 2, "tp": 4}
    mesh = make_mesh(n_devices=8)
    assert dict(mesh.shape) == {"dp": 1, "tp": 8}


def test_param_specs_match_param_tree():
    cfg = get_model_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0))
    specs = param_specs(cfg)
    # Same tree structure => tree.map succeeds.
    jax.tree.map(lambda p, s: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_sharded_forward_matches_single_device(eight_devices):
    """The tp-sharded forward must be numerically identical (fp32 CPU) to the
    unsharded one — GSPMD inserts collectives, math unchanged."""
    cfg = get_model_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16)[None, :], (4, 16)).astype(jnp.int32)

    def run(params, cache):
        logits, _ = forward(params, cfg, toks, pos, cache,
                            jnp.zeros((4,), jnp.int32),
                            jnp.full((4,), 16, jnp.int32))
        return logits

    base = run(params, init_cache(cfg, 4, 16, dtype=jnp.float32))

    mesh = make_mesh(n_devices=8, tp=2)
    sharded_params = shard_params(params, mesh, cfg)
    cache = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, cache_spec(cfg, mesh)))
        if x.ndim == 5 else jax.device_put(x, NamedSharding(mesh, P("dp"))),
        init_cache(cfg, 4, 16, dtype=jnp.float32))
    with jax.sharding.set_mesh(mesh):
        sharded = jax.jit(run)(sharded_params, cache)
    np.testing.assert_allclose(np.asarray(base), np.asarray(sharded),
                               rtol=1e-5, atol=1e-5)


def test_dryrun_multichip_runs():
    import __graft_entry__
    __graft_entry__.dryrun_multichip(8)


def test_entry_compiles():
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    # Compile-check only (lower+compile, no execute — llama-1b on CPU is slow).
    jax.jit(fn).lower(*args).compile()


# ---------------------------------------------------------------------------
# Sharded SERVING (round 2): tp-sharded engine generate == single-device,
# sub-mesh pool partition, overlapped members through TPUBackend.
# ---------------------------------------------------------------------------

def test_tp_sharded_generate_matches_single_device(eight_devices):
    from quoracle_tpu.parallel.mesh import make_mesh
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tok = ByteTokenizer()
    prompts = [tok.encode("hello sharded world", add_bos=True),
               tok.encode("a", add_bos=True),
               tok.encode("the quick brown fox", add_bos=True)]

    plain = GenerateEngine(cfg, params, tok, max_seq=256,
                           prompt_buckets=(32, 64))
    mesh = make_mesh(2, tp=2, devices=eight_devices[:2])
    sharded = GenerateEngine(cfg, params, tok, max_seq=256,
                             prompt_buckets=(32, 64), mesh=mesh)
    # greedy → rng-independent; logits must agree across shardings
    a = plain.generate(prompts, temperature=0.0, max_new_tokens=16)
    b = sharded.generate(prompts, temperature=0.0, max_new_tokens=16)
    assert [r.token_ids for r in a] == [r.token_ids for r in b]


def test_tp_with_dp_sharded_generate(eight_devices):
    from quoracle_tpu.parallel.mesh import make_mesh
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    tok = ByteTokenizer()
    prompts = [tok.encode(f"row {i}", add_bos=True) for i in range(4)]
    plain = GenerateEngine(cfg, params, tok, max_seq=256, prompt_buckets=(32,))
    mesh = make_mesh(4, tp=2, devices=eight_devices[:4])  # dp=2 x tp=2
    sharded = GenerateEngine(cfg, params, tok, max_seq=256,
                             prompt_buckets=(32,), mesh=mesh)
    a = plain.generate(prompts, temperature=0.0, max_new_tokens=8)
    b = sharded.generate(prompts, temperature=0.0, max_new_tokens=8)
    assert [r.token_ids for r in a] == [r.token_ids for r in b]


def test_pool_submeshes_partition(eight_devices):
    from quoracle_tpu.parallel.mesh import pool_submeshes
    meshes = pool_submeshes(3, devices=eight_devices)
    assert len(meshes) == 3
    # 8 devices / 3 members -> 2 each, no overlap among the first three
    used = [d for m in meshes for d in m.devices.flat]
    assert len(set(used)) == 6
    for m in meshes:
        assert int(np.prod(list(m.shape.values()))) == 2


def test_backend_overlapped_members_on_submeshes(eight_devices):
    """Full pool query across tp-sharded members running concurrently —
    results must match the sequential single-device path."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    from quoracle_tpu.parallel.mesh import pool_submeshes
    pool = ["xla:tiny", "xla:tiny-gemma"]
    msgs = [{"role": "user", "content": "pick an action"}]
    reqs = [QueryRequest(s, msgs, temperature=0.0, max_tokens=8)
            for s in pool for _ in range(2)]

    seq_backend = TPUBackend(pool=pool, overlap=False)
    par_backend = TPUBackend(pool=pool,
                             submeshes=pool_submeshes(2, devices=eight_devices,
                                                      tp=2),
                             overlap=True)
    a = seq_backend.query(reqs)
    b = par_backend.query(reqs)
    assert [r.ok for r in a] == [r.ok for r in b] == [True] * 4
    assert [r.text for r in a] == [r.text for r in b]
    seq_backend.close()
    par_backend.close()


def test_member_batcher_coalesces_concurrent_rounds():
    """The member's batcher: concurrent query() calls for the same member
    merge into fewer generate() calls (several agents' rows in one tick),
    made available to real agent trees."""
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend

    backend = TPUBackend(["xla:tiny"])
    engine = backend.engines["xla:tiny"]
    batch_sizes = []
    orig = engine.generate
    gate = threading.Event()

    def slow_generate(prompts, **kw):
        batch_sizes.append(len(prompts))
        if len(batch_sizes) == 1:
            gate.set()          # signal: the first tick is inside
            time.sleep(0.5)     # let the other callers enqueue
        return orig(prompts, **kw)

    engine.generate = slow_generate

    def one_round(agent):
        return backend.query([QueryRequest(
            "xla:tiny", [{"role": "user", "content": f"round {agent}"}],
            temperature=0.0, max_tokens=4, session_id=f"agent-{agent}")])

    with ThreadPoolExecutor(max_workers=3) as ex:
        f0 = ex.submit(one_round, 0)
        gate.wait(timeout=30)             # the worker is mid-generate
        f1 = ex.submit(one_round, 1)
        f2 = ex.submit(one_round, 2)
        all_res = [f.result(timeout=120) for f in (f0, f1, f2)]

    for res in all_res:
        assert res[0].ok, res[0].error
    # rounds 1+2 queued while 0 served -> admitted into ONE tick
    assert batch_sizes[0] == 1
    assert max(batch_sizes) >= 2
    assert sum(batch_sizes) == 3
    # sessions stored per agent despite the merge
    assert all(engine.sessions.get(f"agent-{a}") is not None
               for a in range(3))
    backend.close()


def _shape_kinds(eng) -> set:
    return {str(e["shape"]).split("x")[0]
            for e in eng.compiles.snapshot()["shapes"]}


def test_tp_sharded_ragged_path_matches_gather(eight_devices):
    """A tp mesh serves sessions through the ragged programs, the kernel
    per-tp-shard under shard_map, instead of falling back to gather: on a
    tp=2 mesh they emit the single-device gather path's greedy tokens
    across a session-resumed refinement round with a sessionless
    neighbor row."""
    from quoracle_tpu.parallel.mesh import make_mesh
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tok = ByteTokenizer()

    def run(eng):
        pa = tok.encode("user: compare sharded paged paths", add_bos=True)
        pb = tok.encode("user: sessionless neighbor", add_bos=True)
        r = eng.generate([pa, pb], temperature=0.0, max_new_tokens=8,
                         session_ids=["s", None])
        pa2 = pa + r[0].token_ids + tok.encode(" refine")[0:]
        r2 = eng.generate([pa2, pb], temperature=0.0, max_new_tokens=8,
                          session_ids=["s", None])
        return [x.token_ids for x in r + r2]

    plain = GenerateEngine(cfg, params, tok, max_seq=256,
                           prompt_buckets=(32, 64))
    plain._force_gather_decode = True

    mesh = make_mesh(2, tp=2, devices=eight_devices[:2])
    sharded = GenerateEngine(cfg, params, tok, max_seq=256,
                             prompt_buckets=(32, 64), mesh=mesh)
    assert sharded._ragged_shard is not None
    want, got = run(plain), run(sharded)
    assert got == want
    assert _shape_kinds(sharded) == {"ragged"}


def test_tp_dp_mesh_falls_back_to_gather_and_matches(eight_devices):
    """dp×tp mesh: the flat token-major batch cannot ride a dp axis, so
    the mesh alone routes every paged tick to the gather programs (batch
    on dp, heads on tp), whose tokens are the single-device engine's."""
    from quoracle_tpu.parallel.mesh import make_mesh
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    tok = ByteTokenizer()
    prompts = [tok.encode(f"row {i} with some content", add_bos=True)
               for i in range(4)]
    sids = [f"s{i}" for i in range(4)]

    plain = GenerateEngine(cfg, params, tok, max_seq=256,
                           prompt_buckets=(32, 64))
    mesh = make_mesh(4, tp=2, devices=eight_devices[:4])  # dp=2 x tp=2
    sharded = GenerateEngine(cfg, params, tok, max_seq=256,
                             prompt_buckets=(32, 64), mesh=mesh)
    assert not sharded._ragged_ok
    a = plain.generate(prompts, temperature=0.0, max_new_tokens=8,
                       session_ids=sids)
    b = sharded.generate(prompts, temperature=0.0, max_new_tokens=8,
                         session_ids=sids)
    assert [r.token_ids for r in a] == [r.token_ids for r in b]
    assert _shape_kinds(plain) == {"ragged"}
    assert "ragged" not in _shape_kinds(sharded)
