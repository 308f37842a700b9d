"""The family with a learned selection inside its latent attention
(`families/sparse_latent_moe.py`, PR 31) and what came with it: the
configuration's file against the published `config.json`, the family's
counts, its reference without the program and its two controls (int8; the
selection switched off), the new metrics' readers on a hand-made trace,
the traffic file's lengths. The reference against the program's forward is
`tests/test_sparse_latent.py`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs, spans, traffic
from benchmark.families import latent_moe
from benchmark.families import sparse_latent_moe as fam
from benchmark.tests.test_latent_moe_family import (
    BENCH, US, hand_made, metric, reader,
)

CELL = "deepseek-v3.2-ep16-l5.long-shared-prompt"
NEW = ["step.decode_indexer_share_pct", "step.prefill_indexer_share_pct",
       "sparse.selected_pair_share_pct",
       "kernel.index_scores_roofline_share_pct",
       "kernel.sparse_attn_roofline_share_pct"]
SHARED = ["step.decode_routed_experts_share_pct",
          "step.decode_router_share_pct",
          "step.decode_latent_proj_share_pct",
          "moe.experts_reached_per_layer_step",
          "moe.held_assignment_share_pct",
          "kernel.routed_experts_bw_share_pct"]

# the catalog's `config` of DeepSeek-V3.2-Exp (model-configs guide,
# architectures.jsonl), every key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def raw():
    return configs.load_config("deepseek-v3.2-ep16-l5")


def test_the_file_holds_every_published_key_but_the_four_it_cuts(raw):
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 32320}
    assert raw["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        if key in cut:
            assert raw[key] == cut[key]
            assert raw["reduced_from"][key] == value
        else:
            assert raw[key] == value, key
    assert raw["family"] == "sparse_latent_moe" and raw["chips"] == 1
    assert raw["serve_args"] == [] == raw["control"]["serve_args"]
    assert "16 chips share each layer" in raw["deployment"]
    for said in ("indexer", "router_bias", "num_nextn_predict_layers",
                 "rotary", "torch_dtype", "token_ids"):
        assert said in raw["assumed"]
    assert raw["per_layer"] == SHARED + NEW
    assert configs.family(raw) is fam
    # the accepted family refuses this file, which is why there is another
    with pytest.raises(ValueError, match="correction bias"):
        latent_moe.shapes(raw)


def test_the_familys_counts_follow_from_the_shapes(raw):
    assert fam.stated_precision(raw) == {
        "kv_bytes_per_token": 5 * (640 + 128) * 2}
    attn = 187_107_328 + 13_959_424           # ISSUE 31's counts
    outside = (attn + 3 * 7168 * 18432 + 2 * 7168
               + 4 * (attn + 7168 * 256 + 256 + 44_040_192 + 2 * 7168)
               + 7168 + 32320 * 7168)
    assert fam.decode_weight_bytes(raw) == 2 * outside == 3_633_891_840
    assert fam.decode_step_mark(raw) == {"op_pattern": "^%ragged_attend",
                                         "per_step": 5}
    assert fam.routed_experts_floor_s(raw, 10, PEAKS) \
        == pytest.approx(10 * 88_080_384 / 819e9)
    # a decode step of 8 rows at 12k streams 256 bytes a key a layer; a
    # 512-token suffix over 12k is bound by 2 * 64 * 128 operations a pair
    assert fam.index_scores_floor_s(raw, 8 * 12000, 8 * 12000, PEAKS) \
        == pytest.approx(8 * 12000 * 5 * 256 / 819e9)
    pairs = 512 * 12000
    assert fam.index_scores_floor_s(raw, 12512, pairs, PEAKS) \
        == pytest.approx(pairs * 5 * 2 * 64 * 128 / 197e12)
    # the attention from its SELECTED pairs: a row's 1,280 stored bytes
    # and 128 heads' dot products, the larger (1.56 against 1.50 ns)
    sel = 8 * 2048
    assert fam.sparse_attn_floor_s(raw, sel, PEAKS) == pytest.approx(
        max(sel * 5 * 1280 / 819e9,
            sel * 5 * 2 * 128 * (640 + 512) / 197e12))


TOY = dict(PUBLISHED, name="toy", hidden_size=32, intermediate_size=48,
           kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, num_attention_heads=2,
           num_key_value_heads=2, num_hidden_layers=3,
           first_k_dense_replace=1, moe_intermediate_size=16,
           n_routed_experts=4, n_group=2, topk_group=1,
           num_experts_per_tok=2, vocab_size=64, index_n_heads=2,
           index_head_dim=8, index_topk=8, torch_dtype="bfloat16",
           held_experts_first=4, reduced_from={"n_routed_experts": 8})


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark.tests."
            "test_sparse_latent_moe_family import TOY; "
            "from benchmark.families import sparse_latent_moe as f; "
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
    """The same seed gives the same model; the int8-lowered reference is
    near it and not it (the router's float32 bias is kept as it is); with
    the selection off the first `index_topk` rows are the same rows and
    the later ones are not; a later token moves no earlier row."""
    tokens = np.random.default_rng(3).integers(3, 64, 48).astype(np.int32)
    rows = np.arange(48)
    a = fam.Reference(TOY, 7).logits(tokens, rows)
    assert a.shape == (48, 64) and a.dtype == np.float32
    assert np.array_equal(a, fam.Reference(TOY, 7).logits(tokens, rows))
    low = fam.Reference(TOY, 7)
    bias = np.asarray(low.w["experts"]["router_bias"])
    low.lower_to_int8()
    assert np.array_equal(np.asarray(low.w["experts"]["router_bias"]), bias)
    assert bias.dtype == np.float32 and bias.shape == (2, 8)
    # (near in the mean: at these widths an int8 step flips a selection
    # of 8 keys here and there, and that row moves whole)
    gap = np.abs(a - low.logits(tokens, rows))
    assert 0 < gap.max() and gap.mean() < 0.5 * np.abs(a).mean()
    off = fam.Reference(TOY, 7)
    off.select = False
    b = off.logits(tokens, rows)
    assert np.array_equal(a[:8], b[:8])
    assert np.abs(a[8:] - b[8:]).max() > 1e-3
    later = tokens.copy()
    later[40:] = 5
    assert np.array_equal(
        a[:40], fam.Reference(TOY, 7).logits(later, rows)[:40])


def test_the_new_metrics_read_the_trace_the_ticks_and_the_counters(
        monkeypatch, raw):
    def trace():
        t = hand_made()
        tick = t["host"]["7"][0]
        tick[3].update(index_kv_reads="6000", index_pairs="6000",
                       attn_selected_pairs="4096")
        pre = "jit(step_paged_decode_ragged)/decode_loop/while/body/" \
              "layers/while/body/closed_call/indexer/"
        t["device"][0]["ops"] += [
            ("%fusion.7", 870 * US, 4 * US, pre + "index_proj/dot_general:"),
            ("%index_scores.3", 874 * US, 4 * US,
             pre + "index_scores/jit(index_scores)/index_scores/"
                   "pallas_call:"),
            ("%fusion.8", 878 * US, 2 * US,
             pre + "index_select/reduce_sum:")]
        return t
    monkeypatch.setattr(spans, "trace_of_this_process", trace)
    m = metric(NEW[0])
    assert reader(m).read({}, m) == pytest.approx(100 * 10 / 440)
    m = metric(NEW[1])                  # no indexer in the prefill program
    assert reader(m).read({}, m) is None
    ctx = {"family": fam, "config": raw, "peaks": PEAKS,
           "trace": {"ops": {"%index_scores.3": 4e-6,
                             "%ragged_attend_latent.5": 50e-6}}}
    m = metric(NEW[3])
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * fam.index_scores_floor_s(raw, 6000, 6000, PEAKS) / 4e-6)
    m = metric(NEW[4])
    assert reader(m).read(ctx, m) == pytest.approx(
        100 * fam.sparse_attn_floor_s(raw, 4096, PEAKS) / 50e-6)
    # a program without the spans' arguments (the parent) gives nothing
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    for name in NEW[3:]:
        mm = metric(name)
        assert reader(mm).read(ctx, mm) is None
    from quoracle_tpu.infra.telemetry import METRICS
    m = metric(NEW[2])
    ctx = {"config": {"name": "selected-share-test"}}
    assert reader(m).read(ctx, m) is None
    c = METRICS.counter("quoracle_sparse_attn_pairs_total")
    c.inc(2048, model="selected-share-test", kind="selected")
    c.inc(12288, model="selected-share-test", kind="visible")
    assert reader(m).read(ctx, m) == pytest.approx(100 / 6)


def test_the_manifest_lists_the_new_cells_and_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["config"] == "deepseek-v3.2-ep16-l5"
    assert cells["qwen2.5-3b.long-shared-prompt"]["traffic"] \
        == cells[CELL]["traffic"] == "long-shared-prompt"
    assert all(w["chips"] == 1 for w in cells.values())
    listed = {p["name"]: p for p in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"][0] == CELL     # later cells append
        m = metric(name)
        for k in ("unit", "better", "layer", "source", "moves"):
            assert listed[name][k] == m[k]
    for name in SHARED:
        assert listed[name]["workloads"][:2] == [
            "ax-k1-ep16-l7.agent-turns", CELL]
    assert listed["kernel.latent_attn_roofline_share_pct"]["workloads"] \
        == ["ax-k1-ep16-l7.agent-turns"]     # its floor is the dense walk's
    (cfg,) = [c for c in manifest["configs"]
              if c["name"] == "deepseek-v3.2-ep16-l5"]
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert cfg["source"] == "https://huggingface.co/deepseek-ai/" \
                            "DeepSeek-V3.2-Exp/blob/main/config.json"
    # the form the driver holds an entry's prose to (PR 31 was refused once
    # for a `why` of 220 characters, which no accepted test looked at)
    for entry in manifest["configs"] + manifest["workloads"]:
        why = entry["why"]
        assert 1 <= len(why) <= 200 and why.isascii() and why.isprintable(), \
            (entry["name"], len(why))


def test_the_long_prompt_and_the_mix_realise_their_stated_lengths():
    """Under the repo's tokenizer (the one the rehearsal and every cell
    serve with): the frozen prompt is 10,240 tokens to within 5%, 80
    whole pages; a session's first turn is prompt + task + glue; first
    caps spread from there to the cap; 8 agents 4 s apart."""
    from quoracle_tpu.native.tokenizer import NativeBPETokenizer
    tok = NativeBPETokenizer.for_vocab(32320)
    mix = traffic.load_traffic("long-shared-prompt")
    p = mix["params"]
    with open(os.path.join(BENCH, p["system_prompt"])) as f:
        system = f.read()
    with open(os.path.join(BENCH, "system_prompt.txt")) as f:
        assert system.startswith(f.read().rstrip("\n"))
    n = len(tok.encode(system))
    assert abs(n - 10240) <= 512 and n // 128 == 80
    text = traffic.SeededText(tok, 2 ** 31 + 31)
    clients = traffic.load_generator(mix["kind"]).build(p, 2 ** 31 + 31,
                                                        20, text)
    assert len(clients) == p["agents"] == 8
    caps = sorted(c.first_cap for c in clients)
    assert n + 200 < caps[0] and caps[-1] <= p["session_cap_tokens"] == 14336
    assert caps[-1] - caps[0] > 2500
    first = clients[3].next(None)
    got = len(tok.encode_chat(first.messages))
    task = len(tok.encode(first.messages[1]["content"]))
    assert 40 <= task <= 201 and abs(got - n - task) <= 24
    assert first.think_s >= 3 * p["start_stagger_s"] == 12.0
    assert mix["lead_s"] > 7 * p["start_stagger_s"]
    tools = [len(tok.encode(t[0])) for c in clients for t in c.turns]
    lo, hi = p["tool_tokens"]
    assert lo * 0.95 <= min(tools) and max(tools) <= hi * 1.05
    assert mix["checks"]["reference_pad_to"] == 16384 \
        >= p["session_cap_tokens"] + hi + 128 + 64
