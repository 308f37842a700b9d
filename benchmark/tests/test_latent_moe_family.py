"""The latent-attention, routed-expert family (`families/latent_moe.py`,
PR 27) and what came with it: the configuration's file against the
published `config.json`, the family's byte counts, its reference without
the program, and the three readers its per-layer metrics brought
(`scope_path_share`, `counter_ratio`, `tick_floor_share`) on a hand-made
trace and on the slices recorded on the v5e. The reference against the
program's forward is `tests/test_latent_moe.py`."""

import gzip
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs, spans
from benchmark import trace_reduce as tr
from benchmark.families import latent_moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
US = 1000
CELL = "ax-k1-ep16-l7.agent-turns"
OWN = ["step.decode_routed_experts_share_pct", "step.decode_router_share_pct",
       "step.decode_latent_proj_share_pct",
       "moe.experts_reached_per_layer_step", "moe.held_assignment_share_pct",
       "kernel.routed_experts_bw_share_pct",
       "kernel.latent_attn_roofline_share_pct"]

# https://huggingface.co/skt/A.X-K1/blob/main/config.json, the keys that
# say something of the model's shape
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840}


def metric(name: str) -> dict:
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


def reader(m: dict):
    return importlib.import_module(f"benchmark.readers.{m['reader']}")


@pytest.fixture(scope="module")
def raw():
    return configs.load_config("ax-k1-ep16-l7")


def test_the_file_holds_every_published_key_but_the_three_it_cuts(raw):
    cut = {"num_hidden_layers": 7, "n_routed_experts": 12,
           "vocab_size": 20480}
    assert raw["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        if key in cut:
            assert raw[key] == cut[key]
            assert raw["reduced_from"][key] == value
        else:
            assert raw[key] == value, key
    assert raw["family"] == "latent_moe" and raw["chips"] == 1
    assert raw["serve_args"] == [] == raw["control"]["serve_args"]
    assert "16 chips share each layer" in raw["deployment"]
    for said in ("topk_method", "rotary", "torch_dtype", "token_ids"):
        assert said in raw["assumed"]
    assert raw["per_layer"] == OWN
    assert configs.family(raw) is latent_moe


def test_the_familys_counts_follow_from_the_shapes(raw):
    # 576 values a layer are the model's; 640 lanes are what the TPU stores
    assert latent_moe.stored_lanes(raw) == 640
    assert latent_moe.stated_precision(raw) == {
        "kv_bytes_per_token": 7 * 640 * 2}
    attn = 101_122_048 + 1536 + 512          # ISSUE 27's count, + 2 norms
    outside = (attn + 3 * 7168 * 18432 + 2 * 7168               # layer 0
               + 6 * (attn + 7168 * 192 + 44_040_192 + 2 * 7168)
               + 7168 + 20480 * 7168)                    # norm and head
    assert latent_moe.decode_weight_bytes(raw) == 2 * outside \
        == 3_047_274_496
    assert latent_moe.routed_expert_bytes(raw) == 88_080_384
    assert latent_moe.decode_step_mark(raw) == {
        "op_pattern": "^%ragged_attend", "per_step": 7}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert latent_moe.routed_experts_floor_s(raw, 10, peaks) \
        == pytest.approx(10 * 88_080_384 / 819e9)
    # a decode step of 8 rows at 3k tokens is bound by the latent bytes,
    # a 512-token suffix over 3k by the folded form's operations
    byts = 8 * 3000 * 7 * 1280 / 819e9
    assert latent_moe.latent_attn_floor_s(raw, 8 * 3000, 8 * 3000, peaks) \
        == pytest.approx(byts)
    pairs = 512 * 3000
    assert latent_moe.latent_attn_floor_s(raw, 3512, pairs, peaks) \
        == pytest.approx(pairs * 7 * 2 * 64 * (640 + 512) / 197e12)


TOY = dict(PUBLISHED, name="toy", hidden_size=32, intermediate_size=48,
           kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, num_attention_heads=2,
           num_key_value_heads=2, num_hidden_layers=2,
           moe_intermediate_size=16, n_routed_experts=4, n_group=2,
           topk_group=1, num_experts_per_tok=2, vocab_size=64,
           torch_dtype="bfloat16", held_experts_first=4,
           reduced_from={"n_routed_experts": 8})


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark.tests.test_latent_moe_family "
            "import TOY; from benchmark.families import latent_moe; "
            "import numpy as np; "
            "r = latent_moe.Reference(TOY, 1); "
            "r.logits(np.arange(8, dtype=np.int32), np.arange(8)); "
            "sys.exit(any(m.split('.')[0] == 'quoracle_tpu' "
            "for m in sys.modules))")
    assert subprocess.run(
        [sys.executable, "-c", code], cwd=os.path.dirname(BENCH),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300
    ).returncode == 0


def test_the_reference_and_its_control():
    """The same seed gives the same model; another seed another; the
    lowered reference is near it and not it; an expert's weights do not
    depend on the share that holds it."""
    tokens = np.random.default_rng(3).integers(3, 64, 48).astype(np.int32)
    rows = np.arange(48)
    a = latent_moe.Reference(TOY, 7).logits(tokens, rows)
    assert a.shape == (48, 64) and a.dtype == np.float32
    assert np.array_equal(a, latent_moe.Reference(TOY, 7).logits(tokens,
                                                                 rows))
    assert np.abs(a - latent_moe.Reference(TOY, 8).logits(tokens, rows)
                  ).max() > 0.1
    low = latent_moe.Reference(TOY, 7)
    low.lower_to_int8()
    gap = np.abs(a - low.logits(tokens, rows)).max()
    assert 0 < gap < 0.5 * np.abs(a).max()
    # causal: a later token does not move an earlier row
    later = tokens.copy()
    later[40:] = 5
    assert np.array_equal(
        a[:40], latent_moe.Reference(TOY, 7).logits(later, rows)[:40])
    whole = latent_moe.make_weights(latent_moe.shapes(
        {**TOY, "n_routed_experts": 8, "held_experts_first": 0}), 7)
    share = latent_moe.make_weights(latent_moe.shapes(TOY), 7)
    for leaf in ("we_gate", "we_up", "we_down"):
        assert np.array_equal(np.asarray(whole["experts"][leaf][:, 4:]),
                              np.asarray(share["experts"][leaf]))
    assert np.array_equal(np.asarray(whole["experts"]["router"]),
                          np.asarray(share["experts"]["router"]))


# -- the readers ------------------------------------------------------------

def hand_made():
    """One tick: a prefill program (100-380 us) and a decode program
    (440-880 us) whose expert layer names the family's scopes."""
    worker = [("qtpu.tick", 0, 1000 * US,
               {"model": "m", "rows": "2", "moe_reached": "20",
                "attn_kv_reads": "6000", "attn_pairs": "6000"}),
              ("qtpu.tick.wait_decode", 420 * US, 480 * US, {})]
    mods = [("jit_step_paged_ragged(1)", 100 * US, 280 * US, {}),
            ("jit_step_paged_decode_ragged(2)", 440 * US, 440 * US, {})]
    pre = "jit(step_paged_decode_ragged)/decode_loop/while/body/layers/" \
          "while/body/closed_call/"
    ops = [("%fusion.1", 100 * US, 280 * US,
            "jit(step_paged_ragged)/layers/while/body/closed_call/mlp/"
            "routed_experts/while/body/dot_general:"),
           ("%while.9", 440 * US, 440 * US, ""),
           ("%fusion.2", 440 * US, 60 * US,
            pre + "qkv/latent_proj/dot_general:"),
           ("%ragged_attend_latent.5", 500 * US, 50 * US,
            pre + "attn/jit(ragged_attend_latent)/ragged_attend_latent/"
                  "pallas_call:"),
           ("%fusion.3", 550 * US, 30 * US, pre + "mlp/router/dot_general:"),
           ("%while.11", 580 * US, 200 * US,
            pre + "mlp/routed_experts/while:"),
           ("%fusion.4", 600 * US, 150 * US,
            pre + "mlp/routed_experts/while/body/dot_general:"),
           ("%fusion.5", 780 * US, 50 * US,
            pre + "mlp/shared_expert/dot_general:"),
           ("%fusion.6", 830 * US, 40 * US,
            pre + "mlp/add:")]
    return {"host": {"7": worker},
            "device": {0: {"modules": mods, "ops": ops}}}


def test_scope_path_share_files_by_the_metric_files_own_names(monkeypatch):
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    got = {}
    for name in OWN[:3]:
        m = metric(name)
        assert set(json.load(open(os.path.join(BENCH, "scopes.json")))[
            "scopes"]) < set(m["known_scopes"])
        got[name] = reader(m).read({}, m)
    # of the decode program's 440 us: the experts' loop 200, the router
    # 30, the latent projections 60
    assert got == {OWN[0]: pytest.approx(100 * 200 / 440),
                   OWN[1]: pytest.approx(100 * 30 / 440),
                   OWN[2]: pytest.approx(100 * 60 / 440)}
    # `mlp` is still the whole feed-forward for the reader that is there
    m = metric("step.decode_mlp_share_pct")
    assert reader(m).read({}, m) == pytest.approx(100 * 320 / 440)


def test_scope_path_share_on_the_recorded_dense_program(monkeypatch):
    """The slice recorded on the v5e is a dense model's: none of the
    family's scopes is in it, and the metric is left out; asked for a
    scope that is there, the new reader reads what the old one reads."""
    with gzip.open(os.path.join(HERE, "recorded_v5e_spans.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    trace = {"host": {k: [tuple(e) for e in evs]
                      for k, evs in rec["host"].items()},
             "device": {int(k): {kind: [tuple(e) for e in evs]
                                 for kind, evs in dev.items()}
                        for k, dev in rec["device"].items()}}
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: trace)
    m = metric(OWN[0])
    assert reader(m).read({}, m) is None
    old = metric("step.decode_mlp_share_pct")
    assert reader({**m, "scopes": ["mlp"]}).read(
        {}, {**m, "scopes": ["mlp"]}) == pytest.approx(
        reader(old).read({}, old))
    for name in OWN[5:]:                  # no such tick argument either
        mm = metric(name)
        assert reader(mm).read({"family": latent_moe, "config": {},
                                "peaks": {}, "trace": {"ops": {}}},
                               mm) is None


def test_tick_floor_share_on_the_recorded_v5e_slice(monkeypatch, raw):
    """The kernel's device time from the slice recorded on the v5e
    (`recorded_v5e_slice.json.gz`, PR 23), the work from a tick's
    arguments: floor over time."""
    with gzip.open(os.path.join(HERE, "recorded_v5e_slice.json.gz"),
                   "rt") as f:
        reduced = tr.reduce([tuple(e) for e in json.load(f)])
    kernel_s = sum(tr.matching(reduced["ops"], "^%ragged_attend").values())
    assert kernel_s > 0
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx = {"family": latent_moe, "config": raw, "peaks": peaks,
           "trace": reduced}
    m = metric("kernel.latent_attn_roofline_share_pct")
    want = latent_moe.latent_attn_floor_s(raw, 6000, 6000, peaks)
    assert reader(m).read(ctx, m) == pytest.approx(100 * want / kernel_s)
    # the grouped matmuls: 20 experts reached, over the time under
    # `routed_experts` in both programs of the hand-made trace
    m = metric("kernel.routed_experts_bw_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (20 * 88_080_384 / 819e9) / (480e-6))
    # a family without the function gives nothing
    from benchmark.families import dense
    assert reader(m).read({**ctx, "family": dense}, m) is None


def test_counter_ratio_reads_the_programs_counters():
    from quoracle_tpu.infra.telemetry import METRICS
    ctx = {"config": {"name": "counter-ratio-test"}}
    m = metric("moe.held_assignment_share_pct")
    assert reader(m).read(ctx, m) is None           # nothing booked yet
    c = METRICS.counter("quoracle_moe_assignments_total")
    c.inc(5, model="counter-ratio-test", held="true")
    c.inc(75, model="counter-ratio-test", held="false")
    c.inc(1000, model="another", held="true")
    assert reader(m).read(ctx, m) == pytest.approx(6.25)
    m = metric("moe.experts_reached_per_layer_step")
    METRICS.counter("quoracle_moe_experts_reached_total").inc(
        32, model="counter-ratio-test")
    METRICS.counter("quoracle_moe_layer_steps_total").inc(
        10, model="counter-ratio-test")
    assert reader(m).read(ctx, m) == pytest.approx(3.2)


def test_the_manifest_lists_the_new_cells_and_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["config"] == "ax-k1-ep16-l7"
    assert cells["qwen2.5-3b.cold-prompts"]["traffic"] == "cold-prompts"
    assert all(w["chips"] == 1 for w in cells.values())
    listed = {p["name"]: p for p in manifest["per_layer"]}
    for name in OWN:
        assert listed[name]["workloads"] == [CELL]
        m = metric(name)
        assert {k: listed[name][k] for k in (
            "unit", "better", "layer", "source", "moves")} == \
            {k: m[k] for k in ("unit", "better", "layer", "source", "moves")}
    (cfg,) = [c for c in manifest["configs"] if c["name"] == "ax-k1-ep16-l7"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
