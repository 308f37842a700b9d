"""The family whose conv layers hold state beside the paged KV
(`families/shortconv_moe.py`, PR 33) and what came with it: the
configuration's file against the published `config.json`, the family's
counts, its reference without the program and its two controls (int8; the
conv state zeroed at page boundaries), the new metrics' readers on a
hand-made trace, and the whole command on the CPU at toy widths through a
temporary root. The reference against the program's forward is
`tests/test_shortconv_moe.py`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs, spans
from benchmark.families import shortconv_moe as fam
from benchmark.tests.test_latent_moe_family import BENCH, US, metric, reader

CELL = "lfm2-24b-a2b-l9.agent-turns"
NEW = ["step.decode_conv_share_pct", "step.prefill_conv_share_pct",
       "kernel.short_conv_bw_share_pct", "state.adopted_row_share_pct",
       "state.reprefilled_token_share_pct"]
SHARED = ["step.decode_routed_experts_share_pct",
          "step.decode_router_share_pct",
          "moe.experts_reached_per_layer_step",
          "kernel.routed_experts_bw_share_pct"]

# the catalog's `config` of LFM2-24B-A2B (model-configs guide,
# architectures.jsonl), every key
TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 \
    + ["full_attention", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def raw():
    return configs.load_config("lfm2-24b-a2b-l9")


def test_the_file_holds_every_published_key_but_the_three_it_cuts(raw):
    cut = {"num_hidden_layers": 9, "num_dense_layers": 1,
           "layer_types": TYPES[1:10]}
    assert len(TYPES) == 40 and TYPES.count("conv") == 30
    assert raw["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        if key in cut:
            assert raw[key] == cut[key]
            assert raw["reduced_from"][key] == value
        else:
            assert raw[key] == value, key
    assert raw["family"] == "shortconv_moe" and raw["chips"] == 1
    assert raw["serve_args"] == [] == raw["control"]["serve_args"]
    assert "ONE chip holds each layer whole" in raw["deployment"]
    assert "5,177,950,976" in raw["deployment"]
    for said in ("tie_word_embeddings", "gate_normaliser_eps", "expert_bias",
                 "rotary", "qk_norm", "torch_dtype", "token_ids"):
        assert said in raw["assumed"]
    assert raw["per_layer"] == SHARED + NEW
    assert configs.family(raw) is fam


def test_the_familys_counts_follow_from_the_shapes(raw):
    assert fam.stated_precision(raw) == {"kv_bytes_per_token": 4096,
                                         "state_bytes_per_record": 57344}
    # ISSUE 33's counts: what every step reads outside the experts, and
    # four experts a layer
    assert fam.routed_expert_bytes(raw) == 18_874_368
    assert fam.decode_weight_bytes(raw) \
        == 692_146_688 + 8 * 4 * 18_874_368 == 1_296_126_464
    assert fam.decode_step_mark(raw) == {"op_pattern": "^%ragged_attend",
                                         "per_step": 2}
    assert fam.routed_experts_floor_s(raw, 24, PEAKS) \
        == pytest.approx(24 * 18_874_368 / 819e9)
    # the 7 conv operators: a decode step is bound by their 234,967,040
    # weight bytes, a 2,048-token chunk by its 2 · 7 · 16,777,216
    # operations a token (2.44 ms against 0.29)
    read = 234_967_040 / 819e9
    assert fam.short_conv_floor_s(raw, 1, 8, PEAKS) == pytest.approx(read)
    # a loop of 32 steps: the first reads the weights whole, the others
    # what of them a core's 128 MiB of fast memory cannot hold
    assert fam.short_conv_floor_s(raw, 33, 8, PEAKS) == pytest.approx(
        2 * read + 31 * (234_967_040 - 128 * 2 ** 20) / 819e9)
    assert fam.short_conv_floor_s(raw, 2, 8, PEAKS) == pytest.approx(2 * read)
    assert fam.short_conv_floor_s(raw, 1, 2048, PEAKS) == pytest.approx(
        2 * 7 * 16_777_216 * 2048 / 197e12)
    assert fam.plan(fam.shapes(raw))[1][1] == 2


TOY = dict(PUBLISHED, name="toy", hidden_size=32, intermediate_size=48,
           num_attention_heads=2, num_key_value_heads=2,
           num_hidden_layers=9, num_dense_layers=1,
           layer_types=TYPES[1:10], moe_intermediate_size=16, num_experts=8,
           num_experts_per_tok=2, vocab_size=64, tie_word_embeddings=True,
           gate_normaliser_eps=1e-6, torch_dtype="bfloat16")


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark.tests."
            "test_shortconv_moe_family import TOY; "
            "from benchmark.families import shortconv_moe as f; "
            "import numpy as np; "
            "r = f.Reference(TOY, 1); "
            "r.logits(np.arange(16, dtype=np.int32), np.arange(16)); "
            "sys.exit(any(m.split('.')[0] == 'quoracle_tpu' "
            "for m in sys.modules))")
    assert subprocess.run(
        [sys.executable, "-c", code], cwd=os.path.dirname(BENCH),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300
    ).returncode == 0


def test_the_reference_and_its_two_controls():
    """The same seed gives the same model; a later token moves no earlier
    row; the int8-lowered reference is near it and not it (the router's
    float32 bias is kept as it is); with the state zeroed at every 16th
    token the first 16 rows are the same rows and later ones are not."""
    tokens = np.random.default_rng(3).integers(3, 64, 48).astype(np.int32)
    rows = np.arange(48)
    ref = fam.Reference(TOY, 7)
    a = ref.logits(tokens, rows)
    assert a.shape == (48, 64) and a.dtype == np.float32
    assert np.allclose(a, fam.Reference(TOY, 7).logits(tokens, rows),
                       atol=1e-5)
    assert not np.allclose(a, fam.Reference(TOY, 8).logits(tokens, rows))
    later = tokens.copy()
    later[40:] = 5
    assert np.allclose(ref.logits(later, rows)[:40], a[:40], atol=1e-5)
    ref.zero_state_every = 16
    z = ref.logits(tokens, rows)
    assert np.allclose(z[:16], a[:16], atol=1e-5)
    assert np.abs(z[16:] - a[16:]).max() > 0.05
    ref.zero_state_every = 0
    bias = np.asarray(ref.w["segments"][1][0]["router_bias"])
    ref.lower_to_int8()
    q = ref.logits(tokens, rows)
    # near it on the whole, though a router's near-tie may flip a row's
    # expert at these widths
    assert np.abs(q - a).max() > 1e-4 and np.abs(q - a).mean() < 0.2
    assert np.array_equal(
        np.asarray(ref.w["segments"][1][0]["router_bias"]), bias)
    assert ref.w["embed"][0].dtype == np.int8


# -- the readers ------------------------------------------------------------

def hand_made():
    """One tick: a chunk forward (100-380 us) and a decode program
    (440-880 us) whose conv layers name the family's scopes."""
    worker = [("qtpu.tick", 0, 1000 * US,
               {"model": "m", "rows": "2", "decode_steps": "3",
                "real_tokens": "40", "moe_reached": "20"}),
              ("qtpu.tick.wait_decode", 420 * US, 480 * US, {})]
    mods = [("jit_step_paged_ragged(1)", 100 * US, 280 * US, {}),
            ("jit_step_paged_decode_ragged(2)", 440 * US, 440 * US, {})]
    chunk = "jit(step_paged_ragged)/layers/while/body/closed_call/"
    pre = "jit(step_paged_decode_ragged)/decode_loop/while/body/layers/" \
          "while/body/closed_call/"
    ops = [("%fusion.1", 100 * US, 70 * US, chunk + "conv/conv_in/dot:"),
           ("%fusion.2", 170 * US, 210 * US,
            chunk + "mlp/routed_experts/while/body/dot_general:"),
           ("%while.9", 440 * US, 440 * US, ""),
           ("%fusion.3", 440 * US, 40 * US, pre + "conv/conv_in/dot:"),
           ("%fusion.4", 480 * US, 10 * US, pre + "conv/conv_taps/mul:"),
           ("%fusion.5", 490 * US, 5 * US,
            pre + "conv/state_write/scatter:"),
           ("%fusion.6", 495 * US, 15 * US, pre + "conv/conv_out/dot:"),
           ("%fusion.7", 510 * US, 18 * US, pre + "conv/add:"),
           ("%fusion.8", 530 * US, 20 * US, pre + "qkv/qk_norm/mul:"),
           ("%ragged_attend.5", 550 * US, 30 * US,
            pre + "attn/jit(ragged_attend)/ragged_attend/pallas_call:"),
           ("%fusion.9", 580 * US, 300 * US,
            pre + "mlp/routed_experts/while/body/dot_general:")]
    return {"host": {"7": worker},
            "device": {0: {"modules": mods, "ops": ops}}}


def test_the_new_metrics_read_the_trace_the_ticks_and_the_counters(
        monkeypatch, raw):
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    for name in NEW[:3]:
        assert set(json.load(open(os.path.join(BENCH, "scopes.json")))[
            "scopes"]) < set(metric(name)["known_scopes"])
    # of the decode program's 440 us the conv layers have 88, of the chunk
    # forward's 280 they have 70
    m = metric(NEW[0])
    assert reader(m).read({}, m) == pytest.approx(100 * 88 / 440)
    m = metric(NEW[1])
    assert reader(m).read({}, m) == pytest.approx(100 * 70 / 280)
    # the floor from the tick's arguments (a chunk of 40 tokens and the 2
    # decode steps behind it: two reads of the weights whole, and a third
    # of what fast memory cannot hold) over both programs' time under `conv`
    m = metric(NEW[2])
    ctx = {"family": fam, "config": raw, "peaks": PEAKS, "trace": {"ops": {}}}
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (2 * 234_967_040 + 234_967_040 - 128 * 2 ** 20) / 819e9
        / 158e-6)
    # the accepted expert metric finds this family's floor too
    m = metric("kernel.routed_experts_bw_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (20 * 18_874_368 / 819e9) / 510e-6)
    from quoracle_tpu.infra.telemetry import METRICS
    ctx = {"config": {"name": "state-ratio-test"}}
    for name in NEW[3:]:
        assert reader(metric(name)).read(ctx, metric(name)) is None
    rows = METRICS.counter("quoracle_conv_state_rows_total")
    for source, n in (("carried", 90), ("adopted", 6), ("zero", 4)):
        rows.inc(n, model="state-ratio-test", source=source)
    METRICS.counter("quoracle_conv_state_reprefill_tokens_total").inc(
        30, model="state-ratio-test")
    METRICS.counter("quoracle_sched_real_tokens_total").inc(
        1500, model="state-ratio-test")
    assert reader(metric(NEW[3])).read(ctx, metric(NEW[3])) \
        == pytest.approx(6.0)
    assert reader(metric(NEW[4])).read(ctx, metric(NEW[4])) \
        == pytest.approx(2.0)


def test_the_manifest_lists_the_new_cell_and_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "lfm2-24b-a2b-l9",
                           "traffic": "agent-turns", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    (cfg,) = [c for c in manifest["configs"] if c["name"] == "lfm2-24b-a2b-l9"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    listed = {p["name"]: p for p in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == metric(name)["moves"]
    for name in SHARED:                 # membership: later cells join too
        assert CELL in listed[name]["workloads"]


# -- the whole command, on the CPU, at toy widths ---------------------------

TOY_CELL = dict(TOY, name=None, hidden_size=64, intermediate_size=96,
                num_attention_heads=4, moe_intermediate_size=32,
                vocab_size=512, max_position_embeddings=4096,
                eos_token_id=2, bos_token_id=1,
                serving={"context_window": 4096, "output_limit": 512},
                control={"precision": "the reference lowered to int8",
                         "serve_args": []},
                per_layer=SHARED + NEW, chips=1, serve_args=[],
                family="shortconv_moe")
del TOY_CELL["name"]


def test_the_command_runs_a_toy_of_the_family_end_to_end(capsys, tmp_path):
    """`benchmark.run` on a temporary root that adds a toy configuration
    of this family and its rehearsal cell: the server, the warm-up, the
    closed-loop agents and the comparison with the reference, `correct`
    held to both of the family's stated sizes, and the state's counters
    read by the new metric files."""
    from benchmark import run

    def put(rel, text):
        path = os.path.join(tmp_path, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    put("configs/toy-lfm2-cell.json", json.dumps(TOY_CELL))
    put("cells_rehearsal.json", json.dumps({"workloads": [
        {"name": "toy-lfm2-cell.tiny-turns", "config": "toy-lfm2-cell",
         "traffic": "tiny-turns", "chips": 1}]}))
    with open(os.path.join(BENCH, "warm", "tiny-l2.tiny-turns.json")) as f:
        warm = json.load(f)
    # a toy's bfloat16 router flips near-ties as the real one does
    warm["checks"] = {"reference_gap_max": 2.5,
                      "reference_gap_mean_max": 0.1}
    put("warm/toy-lfm2-cell.tiny-turns.json", json.dumps(warm))
    rc = run.main(["--workload", "toy-lfm2-cell.tiny-turns", "--seed",
                   str(2 ** 31 + 33), "--seconds", "4", "--trace", "1"],
                  root=str(tmp_path))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    assert line["checks"]["kv_bytes_per_token"] == {"value": 256,
                                                    "limit": 256}
    assert line["checks"]["state_bytes_per_record"] == {"value": 1792,
                                                        "limit": 1792}
    assert line["checks"]["warm_keys_missed"]["value"] == 0
    assert line["checks"]["reference_rows_compared"]["value"] > 0
    got = line["metrics"]
    assert 0 < got["state.adopted_row_share_pct"]["value"] < 100
    assert 0 <= got["state.reprefilled_token_share_pct"]["value"] < 50
    assert 2 <= got["moe.experts_reached_per_layer_step"]["value"] <= 8
