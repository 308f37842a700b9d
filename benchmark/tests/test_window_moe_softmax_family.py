"""The family of window and full attention layers mixed with a SOFTMAX
router and nothing else (`families/window_moe_softmax.py`, PR 41) and what
came with it: the configuration's file against the published `config.json`,
the family's counts, its reference without the program and its three
controls (int8; the window lifted; sigmoid scores), the metrics' readers
with this family's floors, the new counter's metric, and the whole command
and both control tools on the CPU at toy widths through a temporary root.
The reference against the program's forward is
`tests/test_window_moe_softmax.py`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs, spans
from benchmark.families import window_moe_softmax as fam
from benchmark.tests.test_latent_moe_family import BENCH, metric, reader
from benchmark.tests.test_window_moe_family import hand_made

CONFIG = "mellum2-12b-a2.5b-l12"
CELL = CONFIG + ".agent-turns"
NEW = ["moe.block_fill_share_pct"]
SHARED = ["step.decode_routed_experts_share_pct",
          "step.decode_router_share_pct",
          "moe.experts_reached_per_layer_step",
          "moe.held_assignment_share_pct",
          "kernel.routed_experts_bw_share_pct",
          "step.decode_window_attn_share_pct",
          "step.decode_full_attn_share_pct",
          "step.prefill_full_attn_share_pct", "kv.window_held_share_pct",
          "kernel.window_attn_roofline_share_pct",
          "kernel.full_attn_roofline_share_pct"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LISTS = ["layer_types", "mlp_layer_types"]
PERIOD = [fam.SLIDING] * 3 + [fam.FULL]


def published() -> dict:
    """The catalog's `config` of Mellum2-12B-A2.5B-Instruct (model-configs
    guide, architectures.jsonl), every key; where the guide is not at
    hand, the file's own keys with `reduced_from` laid over them (the cut
    keys are then only checked against themselves)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                entry = json.loads(line)
                if entry["name"] == "Mellum2-12B-A2.5B-Instruct":
                    return entry["config"]
    raw = configs.load_config(CONFIG)
    keys = ["attention_bias", "head_dim", "hidden_act", "hidden_size",
            "intermediate_size", "max_position_embeddings",
            "max_window_layers", "model_type", "moe_intermediate_size",
            "norm_topk_prob", "num_attention_heads", "num_experts",
            "num_experts_per_tok", "num_hidden_layers",
            "num_key_value_heads", "rms_norm_eps", "rope_parameters",
            "sliding_window", "tie_word_embeddings", "vocab_size",
            "use_sliding_window", *LISTS]
    return {**{k: raw[k] for k in keys}, **raw["reduced_from"]}


@pytest.fixture(scope="module")
def raw():
    return configs.load_config(CONFIG)


def test_the_file_holds_every_published_key_but_those_it_cuts(raw):
    pub = published()
    cut = {"num_hidden_layers": 12, **{k: pub[k][:12] for k in LISTS}}
    assert raw["reduced"] == list(cut)
    assert len(pub["layer_types"]) == 28 == pub["num_hidden_layers"]
    assert pub["layer_types"] == PERIOD * 7
    for key, value in pub.items():
        if key in cut:
            assert raw[key] == cut[key], key
            assert raw["reduced_from"][key] == value, key
        else:
            assert raw[key] == value, key
    # depth is the only cut: every width, head count, the window, all 64
    # experts, 8 a token and the whole vocabulary are the published ones
    for key, value in (("hidden_size", 2304), ("head_dim", 128),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("sliding_window", 1024),
                       ("moe_intermediate_size", 896), ("num_experts", 64),
                       ("num_experts_per_tok", 8), ("vocab_size", 98304),
                       ("intermediate_size", 7168)):
        assert raw[key] == value
    assert raw["layer_types"] == PERIOD * 3
    assert raw["family"] == "window_moe_softmax" and raw["chips"] == 1
    assert raw["serve_args"] == [] == raw["control"]["serve_args"]
    for said in ("ONE chip holds each layer whole", "12 + 8 + 8",
                 "layers 0-11", "5,465,956,608", "10.18 GiB",
                 "All 64 experts", "6,144", "18,432"):
        assert said in raw["deployment"], said
    for said in ("norms", "router_scores", "rotary", "window_layers",
                 "intermediate_size", "prediction_head", "torch_dtype",
                 "token_ids", "weights", "serving"):
        text = raw["assumed"][said]
        assert len(text) > 40 and text.endswith("."), said
    assert "softmax" in raw["assumed"]["router_scores"]
    assert "none is built" in raw["assumed"]["prediction_head"]
    assert raw["per_layer"] == SHARED + NEW
    assert configs.family(raw) is fam


def test_the_familys_counts_follow_from_the_shapes(raw):
    s = fam.shapes(raw)
    assert (s["E"], s["k"], s["H"], s["KV"], s["L"]) == (64, 8, 32, 4, 12)
    assert s["rotary"][fam.FULL]["r"] == 128
    assert s["rotary"][fam.FULL]["yarn"] == (16.0, 32.0, 1.0, 8192,
                                             1.2772588722239782)
    assert s["rotary"][fam.SLIDING] == dict(r=128, theta=500000.0, yarn=None)
    assert fam.stated_precision(raw) == {"kv_bytes_per_token": 6144,
                                         "window_kv_bytes_per_token": 18432}
    assert fam.routed_expert_bytes(raw) == 12_386_304
    # ISSUE 41's counts: a layer's attention, router, experts; the model
    attn, router, expert = 21_233_664, 147_456, 6_193_152
    layer = attn + router + 4_608 + 64 * expert
    assert layer == 417_747_456
    total = 12 * layer + 2 * 226_492_416 + 2_304
    assert total == 5_465_956_608
    assert fam.decode_weight_bytes(raw) == 2 * (
        12 * (attn + router) + 226_492_416 + 12 * 8 * expert) \
        == 2_155_216_896
    assert fam.decode_step_mark(raw) == {"op_pattern": "^%ragged_attend",
                                         "per_step": 12}
    assert fam.routed_experts_floor_s(raw, 42, PEAKS) \
        == pytest.approx(42 * 12_386_304 / 819e9)
    # a decode step of 8 rows at 2,500 tokens: a sliding layer streams 9
    # pages a row and a full layer 20, both bound by their bytes
    rows = 8
    assert fam.window_attn_floor_s(raw, rows * 9 * 128, rows * 1024, PEAKS) \
        == pytest.approx(9 * rows * 9 * 128 * 2048 / 819e9)
    assert fam.full_attn_floor_s(raw, rows * 20 * 128, rows * 2500, PEAKS) \
        == pytest.approx(3 * rows * 20 * 128 * 2048 / 819e9)
    # a 512-token chunk's queries on 2,560 resident tokens in a full
    # layer: bound by its multiplies, 32 query heads
    pairs = 512 * 2304
    assert fam.full_attn_floor_s(raw, 20 * 128, pairs, PEAKS) \
        == pytest.approx(3 * pairs * 4 * 128 * 32 / 197e12)
    assert fam.plan(s) == [([], 0), ([(t, True) for t in PERIOD], 3),
                           ([], 0)]


def test_the_program_is_told_the_same_model(raw):
    from quoracle_tpu.models.config import get_model_config
    cfg = get_model_config(fam.register(raw))
    assert cfg.n_params == 5_465_956_608
    assert cfg.kv_groups == ((None, 3), (1024, 9))
    assert cfg.layer_plan[0] == ((), 0) and cfg.layer_plan[1][1] == 3
    m = cfg.moe
    assert (m.n_routed, m.n_held, m.per_token, m.expert_dim, m.n_shared,
            m.first_dense, m.score, m.routed_scale, m.norm_topk) \
        == (64, 64, 8, 896, 0, 0, "softmax", 1.0, True)
    assert [k.n_heads for _, k in cfg.attn_kinds] == [32, 32]
    assert not cfg.attn_gate and not cfg.tie_embeddings
    full = cfg.attn_kind(fam.FULL)
    assert full.rope_scaling == ("yarn", 16.0, 32.0, 1.0, 8192, 1.0, 0.0)
    assert full.rotary_dim is None and full.rope_theta == 500000.0
    assert cfg.kv_bytes_per_token(group=0) == 6144
    assert cfg.kv_bytes_per_token(group=1) == 18432


L = 8
TOY = dict(published(), name="toy", hidden_size=32, intermediate_size=48,
           num_attention_heads=8, num_key_value_heads=1, head_dim=16,
           num_hidden_layers=L, layer_types=PERIOD * 2,
           mlp_layer_types=["sparse"] * L, sliding_window=24,
           moe_intermediate_size=16, num_experts=16, num_experts_per_tok=8,
           vocab_size=64, torch_dtype="bfloat16",
           max_position_embeddings=4096)
TOY["rope_parameters"] = {
    fam.FULL: dict(TOY["rope_parameters"][fam.FULL],
                   original_max_position_embeddings=32),
    fam.SLIDING: TOY["rope_parameters"][fam.SLIDING]}


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark.tests."
            "test_window_moe_softmax_family import TOY; "
            "from benchmark.families import window_moe_softmax as f; "
            "import numpy as np; "
            "r = f.Reference(TOY, 1); "
            "r.logits(np.arange(16, dtype=np.int32), np.arange(16)); "
            "sys.exit(any(m.split('.')[0] == 'quoracle_tpu' "
            "for m in sys.modules))")
    assert subprocess.run(
        [sys.executable, "-c", code], cwd=os.path.dirname(BENCH),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300
    ).returncode == 0


def test_the_reference_and_its_three_controls():
    """The same seed gives the same model; a later token moves no earlier
    row; the int8-lowered reference is near it and not it; with the window
    lifted the first 24 rows (the window) are the same rows and later ones
    are not; with sigmoid scores every row moves, the first too."""
    tokens = np.random.default_rng(3).integers(3, 64, 64).astype(np.int32)
    rows = np.arange(64)
    ref = fam.Reference(TOY, 7)
    a = ref.logits(tokens, rows)
    assert a.shape == (64, 64) and a.dtype == np.float32
    assert np.allclose(a, fam.Reference(TOY, 7).logits(tokens, rows),
                       atol=1e-5)
    assert not np.allclose(a, fam.Reference(TOY, 8).logits(tokens, rows))
    later = tokens.copy()
    later[40:] = 5
    assert np.allclose(ref.logits(later, rows)[:40], a[:40], atol=1e-5)
    ref.lift_window = True
    z = ref.logits(tokens, rows)
    assert np.allclose(z[:24], a[:24], atol=1e-5)
    assert np.abs(z[40:] - a[40:]).max() > 0.01
    ref.lift_window = False
    ref.sigmoid_router = True
    g = ref.logits(tokens, rows)
    assert np.abs(g - a).max(-1).min() > 1e-3
    ref.sigmoid_router = False
    assert np.allclose(ref.logits(tokens, rows), a, atol=1e-5)
    ref.lower_to_int8()
    q = ref.logits(tokens, rows)
    assert np.abs(q - a).max() > 1e-4 and np.abs(q - a).mean() < 0.2
    assert ref.w["embed"][0].dtype == np.int8


# -- the readers ------------------------------------------------------------

def test_the_metrics_read_this_familys_floors_and_the_new_counter(
        monkeypatch, raw):
    """The accepted metric files on PR 39's hand-made trace, with THIS
    family's counts behind them (2,048 bytes a resident token a layer, 9
    sliding and 3 full layers, 12,386,304 bytes an expert reached), and
    the new metric on the new counter."""
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    ctx = {"family": fam, "config": raw, "peaks": PEAKS, "trace": {"ops": {}}}
    m = metric("kernel.window_attn_roofline_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (9 * 1280 * 2048 / 819e9) / 20e-6)
    m = metric("kernel.full_attn_roofline_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (3 * 24064 * 2048 / 819e9) / 120e-6)
    m = metric("kernel.routed_experts_bw_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (20 * 12_386_304 / 819e9) / 430e-6)
    from quoracle_tpu.infra.telemetry import METRICS
    ctx = {"config": {"name": "block-fill-test"}}
    m = metric(NEW[0])
    assert m["reader"] == "counter_ratio" and m["layer"] == "model step"
    # a program without the counter (the parent commit), or one whose
    # experts the loop served: nothing to read, and nothing raised
    assert reader(m).read(ctx, m) is None
    rows = METRICS.counter("quoracle_moe_block_rows_total")
    rows.inc(1024, model="block-fill-test", kind="assigned")
    rows.inc(64 * 128, model="block-fill-test", kind="run")
    assert reader(m).read(ctx, m) == pytest.approx(12.5)


def test_the_manifest_lists_the_new_cell_and_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "agent-turns", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    assert manifest["workloads"][-1]["name"] == CELL
    (cfg,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert cfg["reduced"] == ["num_hidden_layers", *LISTS]
    assert cfg["source"] == configs.load_config(CONFIG)["source"]
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    listed = {p["name"]: p for p in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == metric(name)["moves"]
    for name in SHARED:
        assert listed[name]["workloads"][-1] == CELL
    with open(os.path.join(BENCH, "warm", f"{CELL}.json")) as f:
        warm = json.load(f)
    assert [4096, 128] in warm["keys"] and len(warm["checks"]["why"]) > 200


# -- the whole command and the control tools, on the CPU, at toy widths -----

TOY_CELL = dict(TOY, hidden_size=64, intermediate_size=96, head_dim=16,
                num_hidden_layers=4, layer_types=PERIOD,
                mlp_layer_types=["sparse"] * 4, num_attention_heads=8,
                num_key_value_heads=1, sliding_window=160,
                moe_intermediate_size=32, vocab_size=512, eos_token_id=2,
                bos_token_id=1,
                serving={"context_window": 4096, "output_limit": 512},
                control={"precision": "the reference lowered to int8",
                         "serve_args": []},
                per_layer=SHARED + NEW, chips=1, serve_args=[],
                family="window_moe_softmax")
del TOY_CELL["name"]
TOY_NAME = "toy-mellum-cell.tiny-turns"


@pytest.fixture()
def toy_root(tmp_path):
    def put(rel, text):
        path = os.path.join(tmp_path, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    put("configs/toy-mellum-cell.json", json.dumps(TOY_CELL))
    put("cells_rehearsal.json", json.dumps({"workloads": [
        {"name": TOY_NAME, "config": "toy-mellum-cell",
         "traffic": "tiny-turns", "chips": 1}]}))
    with open(os.path.join(BENCH, "warm", "tiny-l2.tiny-turns.json")) as f:
        warm = json.load(f)
    # a toy's bfloat16 router flips near-ties as the real one does: sound
    # runs read a mean gap up to 0.004 here, sigmoid scores 0.03 to 0.19
    warm["checks"] = {"reference_gap_max": 1.0,
                      "reference_gap_mean_max": 0.012}
    put(f"warm/{TOY_NAME}.json", json.dumps(warm))
    return str(tmp_path)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_command_runs_a_toy_of_the_family_end_to_end(capsys, toy_root):
    """`benchmark.run` on a temporary root that adds a toy configuration
    of this family and its rehearsal cell: the server, the warm-up, the
    closed-loop agents and the comparison with the reference, `correct`
    held to both of the family's stated sizes; every expert held, so the
    held share reads 100; the block-fill metric finds nothing where the
    loop over blocks served (no TPU) and the line leaves it out."""
    from benchmark import run
    rc = run.main(["--workload", TOY_NAME, "--seed", str(2 ** 31 + 41),
                   "--seconds", "6", "--trace", "1"], root=toy_root)
    assert rc == 0
    line = last_line(capsys)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["checks"]["kv_bytes_per_token"] == {"value": 64, "limit": 64}
    assert line["checks"]["window_kv_bytes_per_token"] == {"value": 192,
                                                           "limit": 192}
    assert line["checks"]["warm_keys_missed"]["value"] == 0
    assert line["checks"]["reference_rows_compared"]["value"] > 0
    got = line["metrics"]
    assert 0 < got["kv.window_held_share_pct"]["value"] <= 100
    assert got["moe.held_assignment_share_pct"]["value"] == 100
    assert got["moe.experts_reached_per_layer_step"]["value"] > 8
    assert "moe.block_fill_share_pct" not in got


def test_the_routers_control_fails_a_limit_on_the_toy(capsys, toy_root):
    """`benchmark.control_router`: the sound run ends correct and the
    reference with sigmoid scores in softmax's place fails a limit."""
    from benchmark import control_router
    rc = control_router.main(["--workload", TOY_NAME, "--seed", "77",
                              "--seconds", "6", "--trace", "0"],
                             root=toy_root)
    out = [json.loads(ln.split(" ", 1)[1]) for ln in
           capsys.readouterr().out.splitlines()
           if ln.startswith("[control] ")][-1]
    assert out["correct"] is True
    swapped = out["reference_with_sigmoid_scores"]
    assert swapped["within_both_limits"] is False and rc == 0
    assert swapped["gap_mean"] > 3 * out["sound"]["reference_gap_mean"]


def test_the_windows_control_runs_on_the_cell_as_it_is(capsys, toy_root):
    """`benchmark.control_window` (PR 39's, unedited) finds what it asks
    a family for — `Reference.lift_window`, `lower_to_int8` — here too."""
    from benchmark import control_window
    control_window.main(["--workload", TOY_NAME, "--seed", "78",
                         "--seconds", "6", "--trace", "0"], root=toy_root)
    out = [json.loads(ln.split(" ", 1)[1]) for ln in
           capsys.readouterr().out.splitlines()
           if ln.startswith("[control] ")][-1]
    assert out["correct"] is True
    lifted = out["reference_with_the_window_lifted"]
    assert lifted["tokens"] > 0 and lifted["gap_mean"] > 0
    assert out["reference_lowered_to_int8"]["tokens"] == lifted["tokens"]
