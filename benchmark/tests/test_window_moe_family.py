"""The family whose window and full attention layers are mixed
(`families/window_moe.py`, PR 39) and what came with it: the configuration's
file against the published `config.json`, the family's counts, its reference
without the program and its two controls (int8; the window lifted), the new
metrics' readers on a hand-made trace, and the whole command on the CPU at
toy widths through a temporary root. The reference against the program's
forward is `tests/test_window_moe.py`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs, spans
from benchmark.families import window_moe as fam
from benchmark.tests.test_latent_moe_family import BENCH, US, metric, reader

CELL = "laguna-s-2.1-ep8-l13.long-shared-prompt"
NEW = ["step.decode_window_attn_share_pct", "step.decode_full_attn_share_pct",
       "step.prefill_full_attn_share_pct", "kv.window_held_share_pct",
       "kernel.window_attn_roofline_share_pct",
       "kernel.full_attn_roofline_share_pct"]
SHARED = ["step.decode_routed_experts_share_pct",
          "step.decode_router_share_pct",
          "moe.experts_reached_per_layer_step",
          "moe.held_assignment_share_pct",
          "kernel.routed_experts_bw_share_pct"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LISTS = ["layer_types", "mlp_layer_types", "gating_types",
         "num_attention_heads_per_layer"]


def published() -> dict:
    """The catalog's `config` of Laguna-S-2.1 (model-configs guide,
    architectures.jsonl), every key; where the guide is not at hand, the
    file's own `reduced_from` laid over it (the cut keys are then only
    checked against themselves)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                entry = json.loads(line)
                if entry["name"] == "Laguna-S-2.1":
                    return entry["config"]
    raw = configs.load_config("laguna-s-2.1-ep8-l13")
    keys = ["model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "max_position_embeddings",
            "attention_bias", "rms_norm_eps", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "norm_topk_prob",
            "decoder_sparse_step", "mlp_only_layers", "tie_word_embeddings",
            "gating", "sliding_window", "rope_parameters",
            "moe_apply_router_weight_on_input", "moe_routed_scaling_factor",
            "moe_router_logit_softcapping", *LISTS]
    return {**{k: raw[k] for k in keys}, **raw["reduced_from"]}


@pytest.fixture(scope="module")
def raw():
    return configs.load_config("laguna-s-2.1-ep8-l13")


def test_the_file_holds_every_published_key_but_those_it_cuts(raw):
    pub = published()
    cut = {"num_hidden_layers": 13, "num_experts": 32, "vocab_size": 25088,
           **{k: pub[k][:13] for k in LISTS}}
    assert raw["reduced"] == list(cut)
    assert len(pub["layer_types"]) == 48 == pub["num_hidden_layers"]
    for key, value in pub.items():
        if key in cut:
            assert raw[key] == cut[key], key
            assert raw["reduced_from"][key] == value, key
        else:
            assert raw[key] == value, key
    # no width, head count, window, expert width, experts a token or
    # router width is cut
    for key, value in (("hidden_size", 3072), ("head_dim", 128),
                       ("num_key_value_heads", 8), ("sliding_window", 512),
                       ("moe_intermediate_size", 1024),
                       ("num_experts_per_tok", 10),
                       ("intermediate_size", 12288)):
        assert raw[key] == value
    assert sorted(set(raw["num_attention_heads_per_layer"])) == [48, 72]
    assert raw["layer_types"].count("full_attention") == 4
    assert raw["family"] == "window_moe" and raw["chips"] == 1
    assert raw["serve_args"] == [] == raw["control"]["serve_args"]
    for said in ("EIGHT chips share each layer", "experts 0-31",
                 "25,088 rows", "layers 0-12", "4,759,004,160"):
        assert said in raw["deployment"], said
    for said in ("norms", "gate", "router_scores", "shared_expert", "rotary",
                 "sliding_window", "torch_dtype", "token_ids", "weights"):
        assert len(raw["assumed"][said]) > 40
    assert "sigmoid" in raw["assumed"]["router_scores"]
    assert raw["per_layer"] == SHARED + NEW
    assert configs.family(raw) is fam


def test_the_familys_counts_follow_from_the_shapes(raw):
    s = fam.shapes(raw)
    assert (s["E"], s["held"], s["k"]) == (256, 32, 10)
    assert s["heads"] == {fam.FULL: 48, fam.SLIDING: 72}
    assert s["rotary"][fam.FULL]["r"] == 64
    assert s["rotary"][fam.SLIDING] == dict(r=128, theta=10000.0, yarn=None)
    assert fam.stated_precision(raw) == {"kv_bytes_per_token": 16384,
                                         "window_kv_bytes_per_token": 36864}
    assert fam.routed_expert_bytes(raw) == 18_874_368
    # ISSUE 39's counts: a full layer's attention, a sliding layer's
    assert fam._attn_params(s, fam.FULL) == 44_187_648
    assert fam._attn_params(s, fam.SLIDING) == 63_135_744
    outside = (4 * 44_187_648 + 9 * 63_135_744 + 3 * 3072 * 12288
               + 12 * (3072 * 256 + 9_437_184) + 25088 * 3072)
    assert fam.decode_weight_bytes(raw) == 2 * outside == 2_115_944_448
    assert fam.decode_step_mark(raw) == {"op_pattern": "^%ragged_attend",
                                         "per_step": 13}
    assert fam.routed_experts_floor_s(raw, 24, PEAKS) \
        == pytest.approx(24 * 18_874_368 / 819e9)
    # a decode step of 8 rows at 12,000 tokens: a sliding layer streams 5
    # pages a row and is bound by its bytes, as a full layer is by its 94
    rows, ctx = 8, 12_000
    assert fam.window_attn_floor_s(raw, rows * 5 * 128, rows * 512, PEAKS) \
        == pytest.approx(9 * rows * 5 * 128 * 4096 / 819e9)
    assert fam.full_attn_floor_s(raw, rows * 94 * 128, rows * ctx, PEAKS) \
        == pytest.approx(4 * rows * 94 * 128 * 4096 / 819e9)
    # a 512-token chunk's queries in a sliding layer: bound by its multiplies
    pairs = 512 * 512
    assert fam.window_attn_floor_s(raw, 9 * 128, pairs, PEAKS) \
        == pytest.approx(9 * pairs * 4 * 128 * 72 / 197e12)
    assert fam.plan(s)[1][1] == 3 and len(fam.plan(s)[1][0]) == 4


L = 9
TYPES = [fam.FULL if i % 4 == 0 else fam.SLIDING for i in range(L)]
TOY = dict(published(), name="toy", hidden_size=32, intermediate_size=48,
           num_attention_heads=6, num_key_value_heads=1, head_dim=16,
           num_hidden_layers=L, layer_types=TYPES,
           mlp_layer_types=["dense"] + ["sparse"] * (L - 1),
           gating_types=["per_head"] * L,
           num_attention_heads_per_layer=[6 if t == fam.FULL else 9
                                          for t in TYPES],
           sliding_window=24, moe_intermediate_size=16,
           shared_expert_intermediate_size=16, num_experts=4,
           num_experts_per_tok=3, vocab_size=64,
           reduced_from={"num_experts": 16}, torch_dtype="bfloat16",
           max_position_embeddings=4096)
TOY["rope_parameters"] = {
    fam.FULL: dict(TOY["rope_parameters"][fam.FULL],
                   original_max_position_embeddings=32),
    fam.SLIDING: TOY["rope_parameters"][fam.SLIDING]}


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark.tests."
            "test_window_moe_family import TOY; "
            "from benchmark.families import window_moe as f; "
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
    row; the int8-lowered reference is near it and not it; with the window
    lifted the first 24 rows (the window) are the same rows and later ones
    are not."""
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
    ref.lower_to_int8()
    q = ref.logits(tokens, rows)
    assert np.abs(q - a).max() > 1e-4 and np.abs(q - a).mean() < 0.2
    assert ref.w["embed"][0].dtype == np.int8


# -- the readers ------------------------------------------------------------

def hand_made():
    """One tick: a chunk forward (100-380 us) and a decode program
    (440-880 us) whose attention layers carry their kind's scope."""
    worker = [("qtpu.tick", 0, 1000 * US,
               {"model": "m", "rows": "2", "decode_steps": "3",
                "real_tokens": "40", "moe_reached": "20",
                "attn_kv_streamed": "24064", "attn_pairs": "24000",
                "attn_kv_streamed_window": "1280",
                "attn_pairs_window": "1024",
                "window_pages_released": "1"}),
              ("qtpu.tick.wait_decode", 420 * US, 480 * US, {})]
    mods = [("jit_step_paged_ragged(1)", 100 * US, 280 * US, {}),
            ("jit_step_paged_decode_ragged(2)", 440 * US, 440 * US, {})]
    chunk = "jit(step_paged_ragged)/layers/while/body/closed_call/"
    pre = "jit(step_paged_decode_ragged)/decode_loop/while/body/layers/" \
          "while/body/closed_call/"
    kernel = "attn/jit(ragged_attend)/ragged_attend/pallas_call:"
    ops = [("%fusion.1", 100 * US, 60 * US,
            chunk + "full_attention/qkv/dot_general:"),
           ("%ragged_attend.2", 160 * US, 80 * US,
            chunk + "full_attention/" + kernel),
           ("%ragged_attend.3", 240 * US, 10 * US,
            chunk + "sliding_attention/" + kernel),
           ("%fusion.2", 250 * US, 130 * US,
            chunk + "mlp/routed_experts/while/body/dot_general:"),
           ("%while.9", 440 * US, 440 * US, ""),
           ("%fusion.3", 440 * US, 40 * US,
            pre + "sliding_attention/qkv/dot_general:"),
           ("%ragged_attend.5", 480 * US, 10 * US,
            pre + "sliding_attention/" + kernel),
           ("%fusion.4", 490 * US, 16 * US,
            pre + "sliding_attention/attn_out/attn_gate/mul:"),
           ("%fusion.5", 510 * US, 20 * US,
            pre + "full_attention/qkv/dot_general:"),
           ("%ragged_attend.6", 530 * US, 40 * US,
            pre + "full_attention/" + kernel),
           ("%fusion.9", 580 * US, 300 * US,
            pre + "mlp/routed_experts/while/body/dot_general:")]
    return {"host": {"7": worker},
            "device": {0: {"modules": mods, "ops": ops}}}


def test_the_new_metrics_read_the_trace_the_ticks_and_the_counters(
        monkeypatch, raw):
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    # a layer's whole token mixer files under its kind: of the decode
    # program's 440 us the sliding layers have 66 and the full ones 60, of
    # the chunk forward's 280 the full layers have 140
    for name, want in zip(NEW[:3], (66 / 440, 60 / 440, 140 / 280)):
        m = metric(name)
        assert not {"qkv", "attn", "attn_out"} & set(m["known_scopes"])
        assert reader(m).read({}, m) == pytest.approx(100 * want)
    # a kernel's roofline: the family's floor from the tick's arguments
    # over the time under the kind's scope that no inner known scope
    # takes — the kernel's, in both programs
    ctx = {"family": fam, "config": raw, "peaks": PEAKS, "trace": {"ops": {}}}
    m = metric(NEW[4])
    assert "attn" not in m["known_scopes"] and "qkv" in m["known_scopes"]
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (9 * 1280 * 4096 / 819e9) / 20e-6)
    m = metric(NEW[5])
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (4 * 24064 * 4096 / 819e9) / 120e-6)
    # the accepted expert metric finds this family's floor too
    m = metric("kernel.routed_experts_bw_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (20 * 18_874_368 / 819e9) / 430e-6)
    from quoracle_tpu.infra.telemetry import METRICS
    ctx = {"config": {"name": "held-ratio-test"}}
    m = metric(NEW[3])
    assert reader(m).read(ctx, m) is None
    held = METRICS.counter("quoracle_kv_session_held_tokens_total")
    held.inc(12_000, model="held-ratio-test", group="full")
    held.inc(640, model="held-ratio-test", group="window")
    assert reader(m).read(ctx, m) == pytest.approx(100 * 640 / 12_000)


def test_the_manifest_lists_the_new_cell_and_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "laguna-s-2.1-ep8-l13",
                           "traffic": "long-shared-prompt", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    (cfg,) = [c for c in manifest["configs"]
              if c["name"] == "laguna-s-2.1-ep8-l13"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", *LISTS]
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    listed = {p["name"]: p for p in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == metric(name)["moves"]
    for name in SHARED:                 # membership: later cells join too
        assert CELL in listed[name]["workloads"]
    with open(os.path.join(BENCH, "warm", f"{CELL}.json")) as f:
        warm = json.load(f)
    assert [16384, 128] in warm["keys"] and len(warm["checks"]["why"]) > 200


# -- the whole command, on the CPU, at toy widths ---------------------------

TOY_CELL = dict(TOY, hidden_size=64, intermediate_size=96, head_dim=16,
                num_hidden_layers=5, layer_types=TYPES[:5],
                mlp_layer_types=["dense"] + ["sparse"] * 4,
                gating_types=["per_head"] * 5,
                num_attention_heads=6, num_key_value_heads=1,
                num_attention_heads_per_layer=[6 if t == fam.FULL else 9
                                               for t in TYPES[:5]],
                sliding_window=160, moe_intermediate_size=32,
                shared_expert_intermediate_size=32, vocab_size=512,
                eos_token_id=2, bos_token_id=1,
                serving={"context_window": 4096, "output_limit": 512},
                control={"precision": "the reference lowered to int8",
                         "serve_args": []},
                per_layer=SHARED + NEW, chips=1, serve_args=[],
                family="window_moe")
del TOY_CELL["name"]


def test_the_command_runs_a_toy_of_the_family_end_to_end(capsys, tmp_path):
    """`benchmark.run` on a temporary root that adds a toy configuration
    of this family and its rehearsal cell: the server, the warm-up, the
    closed-loop agents and the comparison with the reference, `correct`
    held to both of the family's stated sizes, and the groups' counters
    read by the new metric file."""
    from benchmark import run

    def put(rel, text):
        path = os.path.join(tmp_path, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    put("configs/toy-laguna-cell.json", json.dumps(TOY_CELL))
    put("cells_rehearsal.json", json.dumps({"workloads": [
        {"name": "toy-laguna-cell.tiny-turns", "config": "toy-laguna-cell",
         "traffic": "tiny-turns", "chips": 1}]}))
    with open(os.path.join(BENCH, "warm", "tiny-l2.tiny-turns.json")) as f:
        warm = json.load(f)
    # a toy's bfloat16 router flips near-ties as the real one does
    warm["checks"] = {"reference_gap_max": 2.5,
                      "reference_gap_mean_max": 0.1}
    put("warm/toy-laguna-cell.tiny-turns.json", json.dumps(warm))
    rc = run.main(["--workload", "toy-laguna-cell.tiny-turns", "--seed",
                   str(2 ** 31 + 39), "--seconds", "6", "--trace", "1"],
                  root=str(tmp_path))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    assert line["checks"]["kv_bytes_per_token"] == {"value": 128,
                                                    "limit": 128}
    assert line["checks"]["window_kv_bytes_per_token"] == {"value": 192,
                                                           "limit": 192}
    assert line["checks"]["warm_keys_missed"]["value"] == 0
    assert line["checks"]["reference_rows_compared"]["value"] > 0
    got = line["metrics"]
    assert 0 < got["kv.window_held_share_pct"]["value"] <= 100
    assert 0 < got["moe.held_assignment_share_pct"]["value"] < 100
