"""The family whose Mamba-2 layers hold their state in a pool of records
beside the paged KV (`families/mamba_moe.py`, PR 47) and what came with it:
the configuration's file against the published `config.json`, the family's
counts, its reference without the program and its two controls (int8; the
state zeroed at page boundaries), the new metrics' readers on a hand-made
trace, and the whole command and `control_state` on the CPU at toy widths
through a temporary root. The reference against the program's forward is
`tests/test_mamba_moe.py`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs, spans
from benchmark.families import mamba_moe as fam
from benchmark.tests.test_latent_moe_family import BENCH, US, metric, reader

CONFIG = "nemotron-3-nano-30b-a3b-ep2-l14"
CELL = CONFIG + ".agent-turns"
NEW = ["step.decode_ssm_share_pct", "step.prefill_ssm_share_pct",
       "kernel.ssm_scan_roofline_share_pct", "kernel.ssm_step_bw_share_pct",
       "ssm.adopted_row_share_pct", "ssm.reprefilled_token_share_pct",
       "ssm.records_held_share_pct"]
SHARED = ["step.decode_routed_experts_share_pct",
          "step.decode_router_share_pct",
          "moe.experts_reached_per_layer_step",
          "moe.held_assignment_share_pct",
          "kernel.routed_experts_bw_share_pct",
          "moe.block_fill_share_pct"]

# the catalog's `config` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
# (model-configs guide, architectures.jsonl), every key
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MAMBA_BYTES = 6 * 2 * (2688 * 10304 + 5 * 6144 + 4096 * 2688)


@pytest.fixture(scope="module")
def raw():
    return configs.load_config(CONFIG)


def test_the_file_holds_every_published_key_but_the_four_it_cuts(raw):
    cut = {"num_hidden_layers": 14, "hybrid_override_pattern": PATTERN[:14],
           "n_routed_experts": 64, "vocab_size": 65536}
    assert len(PATTERN) == 52 and PATTERN[:14] == "MEMEM*E" * 2
    assert [PATTERN.count(c) for c in "ME*"] == [23, 23, 6]
    assert raw["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        if key in cut:
            assert raw[key] == cut[key]
            assert raw["reduced_from"][key] == value
        else:
            assert raw[key] == value, key
    assert raw["family"] == "mamba_moe" and raw["chips"] == 1
    assert raw["serve_args"] == [] == raw["control"]["serve_args"]
    assert raw["held_experts_first"] == 0
    assert "TWO chips share each layer" in raw["deployment"]
    assert "14 + 14 + 14 + 10" in raw["deployment"]
    assert "4,584,903,936" in raw["deployment"]
    for said in ("positional_embedding", "expand", "time_step", "state_dtype",
                 "gate_eps", "n_group_vs_n_groups", "torch_dtype",
                 "token_ids", "weights", "serving"):
        assert said in raw["assumed"]
    assert raw["serving"]["state_records"] == 48
    assert raw["per_layer"] == SHARED + NEW
    assert configs.family(raw) is fam
    # inside the guide's floors: a whole period, >= 4 layers, >= 8 experts,
    # >= 1/8 of the vocabulary
    assert raw["n_routed_experts"] >= 8 and raw["vocab_size"] * 8 >= 131072


def test_the_familys_counts_follow_from_the_shapes(raw):
    assert fam.stated_precision(raw) == {"kv_bytes_per_token": 2048,
                                         "state_bytes_per_record": 12_804_096}
    assert fam.record_bytes(raw) == 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert fam.routed_expert_bytes(raw) == 19_955_712
    # everything outside the routed experts, and of them nothing: a step's
    # rows may all have chosen the other chip's
    outside = (MAMBA_BYTES + 2 * 2 * (2 * 2688 * 4096 + 2 * 2688 * 256)
               + 6 * 2 * (2688 * 128 + 2 * 2688 * 3712) + 2 * 65536 * 2688)
    assert fam.decode_weight_bytes(raw) == outside
    assert MAMBA_BYTES // 6 == 77_475_840        # "77.5 MB" a Mamba operator
    assert fam.decode_step_mark(raw) == {"op_pattern": "^%ragged_attend",
                                         "per_step": 2}
    assert fam.routed_experts_floor_s(raw, 20, PEAKS) \
        == pytest.approx(20 * 19_955_712 / 819e9)
    # the scan: a chunk's operations and bytes in one layer, the larger of
    # the two times, over the 6 layers
    ops, byts = fam.ssm_scan_ops_bytes(raw, 1)
    assert ops == 8 * 2 * 128 ** 3 + 64 * (2 * 128 * 128 * 64
                                           + 4 * 128 * 64 * 128)
    assert byts == 2 * 128 * 4096 * 2 + 2 * 128 * 1024 * 2 \
        + 64 * (3 * 128 + 128) * 4 + 64 * 64 * 128 * 4
    assert fam.ssm_scan_floor_s(raw, 5, PEAKS) == pytest.approx(
        6 * 5 * max(ops / 197e12, byts / 819e9))
    assert byts / 819e9 > ops / 197e12           # bound by its bytes
    # the decode recurrence: the first step of a tick's loop reads the 6
    # operators whole, a later one what fast memory cannot hold, and a
    # row's forward reads and writes its record
    assert fam.ssm_step_floor_s(raw, 1, 0, PEAKS) == 0.0
    assert fam.ssm_step_floor_s(raw, 2, 8, PEAKS) == pytest.approx(
        (MAMBA_BYTES + 8 * 2 * 12_804_096) / 819e9)
    assert fam.ssm_step_floor_s(raw, 33, 200, PEAKS) == pytest.approx(
        (MAMBA_BYTES + 31 * (MAMBA_BYTES - 128 * 2 ** 20)
         + 200 * 2 * 12_804_096) / 819e9)
    assert fam.plan(fam.shapes(raw))[1] == (list("MEMEM*E"), 2)


TOY = dict(PUBLISHED, name="toy", hidden_size=32, mamba_num_heads=4,
           mamba_head_dim=8, n_groups=2, ssm_state_size=8, chunk_size=16,
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           num_hidden_layers=14, hybrid_override_pattern=PATTERN[:14],
           moe_intermediate_size=16, moe_shared_expert_intermediate_size=24,
           n_routed_experts=8, num_experts_per_tok=2, vocab_size=64,
           torch_dtype="bfloat16", held_experts_first=0,
           reduced_from={"n_routed_experts": 16})


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark.tests."
            "test_mamba_moe_family import TOY; "
            "from benchmark.families import mamba_moe as f; "
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
    row; the int8-lowered reference is near it and not it (the float32
    leaves are kept as they are); with the state zeroed at every 16th
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
    kept = {k: np.asarray(ref.w["segments"][1][q][k])
            for q, k in ((1, "router_bias"), (0, "a_log"), (0, "dt_bias"))}
    ref.lower_to_int8()
    q = ref.logits(tokens, rows)
    assert np.abs(q - a).max() > 1e-4 and np.abs(q - a).mean() < 0.5
    for (pos, k) in ((1, "router_bias"), (0, "a_log"), (0, "dt_bias")):
        assert np.array_equal(np.asarray(ref.w["segments"][1][pos][k]),
                              kept[k])
    assert ref.w["embed"][0].dtype == np.int8
    assert ref.w["segments"][1][0]["w_xbc"][0].dtype == np.int8


# -- the readers ------------------------------------------------------------

def hand_made():
    """One tick: a chunk forward (100-380 us) and a decode program
    (440-880 us) whose Mamba layers name the family's scopes."""
    worker = [("qtpu.tick", 0, 1000 * US,
               {"model": "m", "rows": "2", "decode_steps": "3",
                "real_tokens": "300", "moe_reached": "20",
                "ssm_scan_chunks": "4", "ssm_row_steps": "4",
                "ssm_records_held": "12", "ssm_records_pool": "48"}),
              ("qtpu.tick.wait_decode", 420 * US, 480 * US, {})]
    mods = [("jit_step_paged_ragged(1)", 100 * US, 280 * US, {}),
            ("jit_step_paged_decode_ragged(2)", 440 * US, 440 * US, {})]
    chunk = "jit(step_paged_ragged)/layers/while/body/closed_call/"
    pre = "jit(step_paged_decode_ragged)/decode_loop/while/body/layers/" \
          "while/body/closed_call/"
    ops = [("%fusion.1", 100 * US, 40 * US, chunk + "ssm/ssm_in/dot:"),
           ("%ssm_scan.3", 140 * US, 30 * US,
            chunk + "ssm/ssm_scan/jit(ssm_scan)/ssm_scan/pallas_call:"),
           ("%fusion.2", 170 * US, 210 * US,
            chunk + "mlp/routed_experts/while/body/dot_general:"),
           ("%while.9", 440 * US, 440 * US, ""),
           ("%fusion.3", 440 * US, 40 * US, pre + "ssm/ssm_in/dot:"),
           ("%fusion.4", 480 * US, 10 * US, pre + "ssm/ssm_conv/mul:"),
           ("%fusion.5", 490 * US, 25 * US, pre + "ssm/ssm_scan/mul:"),
           ("%fusion.6", 515 * US, 5 * US,
            pre + "ssm/state_write/dynamic_update_slice:"),
           ("%fusion.7", 520 * US, 10 * US, pre + "ssm/ssm_out/dot:"),
           ("%ragged_attend.5", 550 * US, 30 * US,
            pre + "attn/jit(ragged_attend)/ragged_attend/pallas_call:"),
           ("%fusion.9", 580 * US, 300 * US,
            pre + "mlp/routed_experts/while/body/dot_general:")]
    return {"host": {"7": worker},
            "device": {0: {"modules": mods, "ops": ops}}}


def test_the_new_metrics_read_the_trace_the_ticks_and_the_counters(
        monkeypatch, raw):
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    for name in (NEW[0], NEW[1], NEW[3]):
        assert set(json.load(open(os.path.join(BENCH, "scopes.json")))[
            "scopes"]) < set(metric(name)["known_scopes"])
    # of the decode program's 440 us the Mamba layers have 90, of the chunk
    # forward's 280 they have 70
    m = metric(NEW[0])
    assert reader(m).read({}, m) == pytest.approx(100 * 90 / 440)
    m = metric(NEW[1])
    assert reader(m).read({}, m) == pytest.approx(100 * 70 / 280)
    # the scan kernel's 30 us against the floor of the tick's 4 chunks
    ctx = {"family": fam, "config": raw, "peaks": PEAKS,
           "trace": {"ops": {"%ssm_scan.3": 30e-6, "%fusion.1": 40e-6}}}
    m = metric(NEW[2])
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * fam.ssm_scan_floor_s(raw, 4, PEAKS) / 30e-6)
    # the decode recurrence: 2 loop steps, 4 forwards of rows, over the
    # decode program's 90 us under `ssm`
    m = metric(NEW[3])
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * fam.ssm_step_floor_s(raw, 3, 4, PEAKS) / 90e-6)
    # the accepted expert metric finds this family's floor too
    m = metric("kernel.routed_experts_bw_share_pct")
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * (20 * 19_955_712 / 819e9) / 510e-6)
    m = metric(NEW[6])
    assert reader(m).read({}, m) == pytest.approx(25.0)
    from quoracle_tpu.infra.telemetry import METRICS
    ctx = {"config": {"name": "ssm-ratio-test"}}
    for name in NEW[4:6]:
        assert reader(metric(name)).read(ctx, metric(name)) is None
    rows = METRICS.counter("quoracle_ssm_state_rows_total")
    for source, n in (("carried", 90), ("adopted", 6), ("zero", 4)):
        rows.inc(n, model="ssm-ratio-test", source=source)
    METRICS.counter("quoracle_ssm_state_reprefill_tokens_total").inc(
        30, model="ssm-ratio-test")
    METRICS.counter("quoracle_sched_real_tokens_total").inc(
        1500, model="ssm-ratio-test")
    assert reader(metric(NEW[4])).read(ctx, metric(NEW[4])) \
        == pytest.approx(6.0)
    assert reader(metric(NEW[5])).read(ctx, metric(NEW[5])) \
        == pytest.approx(2.0)


def test_the_manifest_lists_the_new_cell_and_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "agent-turns", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    (cfg,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert cfg["source"] == configs.load_config(CONFIG)["source"]
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    listed = {p["name"]: p for p in manifest["per_layer"]}
    for name in NEW:                    # membership: later cells may join
        assert CELL in listed[name]["workloads"]
        assert listed[name]["moves"] == metric(name)["moves"]
    for name in SHARED:
        assert CELL in listed[name]["workloads"]
    # LFM2's record-a-page metrics stay LFM2's
    for name in ("state.adopted_row_share_pct", "step.decode_conv_share_pct",
                 "kernel.short_conv_bw_share_pct"):
        assert CELL not in listed[name]["workloads"]


# -- the whole command, on the CPU, at toy widths ---------------------------

TOY_CELL = dict(TOY, hidden_size=64, mamba_num_heads=8, ssm_state_size=16,
                chunk_size=32, num_attention_heads=32,
                moe_intermediate_size=32,
                moe_shared_expert_intermediate_size=48,
                num_experts_per_tok=6, vocab_size=512,
                max_position_embeddings=4096, eos_token_id=2, bos_token_id=1,
                serving={"context_window": 4096, "output_limit": 512,
                         "state_records": 16},
                control={"precision": "the reference lowered to int8",
                         "serve_args": []},
                per_layer=SHARED + NEW, chips=1, serve_args=[],
                family="mamba_moe")
del TOY_CELL["name"]


def toy_root(tmp_path) -> None:
    def put(rel, text):
        path = os.path.join(tmp_path, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    put("configs/toy-nemotron-cell.json", json.dumps(TOY_CELL))
    put("cells_rehearsal.json", json.dumps({"workloads": [
        {"name": "toy-nemotron-cell.tiny-turns",
         "config": "toy-nemotron-cell", "traffic": "tiny-turns",
         "chips": 1}]}))
    with open(os.path.join(BENCH, "warm", "tiny-l2.tiny-turns.json")) as f:
        warm = json.load(f)
    # a toy's bfloat16 router flips near-ties as the real one does
    warm["checks"] = {"reference_gap_max": 2.5,
                      "reference_gap_mean_max": 0.1}
    put("warm/toy-nemotron-cell.tiny-turns.json", json.dumps(warm))


def test_the_command_runs_a_toy_of_the_family_end_to_end(capsys, tmp_path):
    """`benchmark.run` on a temporary root that adds a toy configuration
    of this family and its rehearsal cell: the server, the warm-up, the
    closed-loop agents and the comparison with the reference, `correct`
    held to both of the family's stated sizes, and the record pool's
    counters read by the new metric files."""
    from benchmark import run
    toy_root(tmp_path)
    rc = run.main(["--workload", "toy-nemotron-cell.tiny-turns", "--seed",
                   str(2 ** 31 + 47), "--seconds", "4", "--trace", "1"],
                  root=str(tmp_path))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    assert line["checks"]["kv_bytes_per_token"] == {"value": 128,
                                                    "limit": 128}
    assert line["checks"]["state_bytes_per_record"] == {
        "value": 6 * (8 * 8 * 16 * 4 + 3 * 128 * 2),
        "limit": 6 * (8 * 8 * 16 * 4 + 3 * 128 * 2)}
    assert line["checks"]["warm_keys_missed"]["value"] == 0
    assert line["checks"]["reference_rows_compared"]["value"] > 0
    got = line["metrics"]
    assert 0 < got["ssm.adopted_row_share_pct"]["value"] < 100
    assert 0 <= got["ssm.reprefilled_token_share_pct"]["value"] < 50
    assert 0 < got["ssm.records_held_share_pct"]["value"] <= 100
    assert 1 <= got["moe.experts_reached_per_layer_step"]["value"] <= 8
    assert 0 < got["moe.held_assignment_share_pct"]["value"] < 100


def test_the_state_control_runs_on_the_new_family_as_it_is(capsys, tmp_path):
    """`benchmark.control_state`, unedited, on the toy cell: the sound run
    is correct, and the reference with the Mamba state and taps zeroed at
    every page boundary is read beside the int8-lowered one."""
    from benchmark import control_state
    toy_root(tmp_path)
    control_state.main(["--workload", "toy-nemotron-cell.tiny-turns",
                        "--seed", str(2 ** 31 + 48), "--seconds", "4",
                        "--trace", "0"], root=str(tmp_path))
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines() if ln.startswith("[control]")]
    got = json.loads(line[len("[control]"):])
    assert got["correct"] is True
    zeroed = got["reference_with_state_zeroed_at_pages"]
    assert zeroed["tokens"] > 0 and zeroed["gap"] > got["sound"][
        "reference_gap"]
    assert got["reference_lowered_to_int8"]["tokens"] == zeroed["tokens"]
