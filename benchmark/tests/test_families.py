"""A configuration names its family, and everything the harness knows of an
architecture is that family's module.

1. The dense family is the code that was in `configs.py` and `reference.py`,
   moved: the same seed gives the same bytes (hashes recorded before the
   move, PR 26), and building its reference imports nothing of the program.
2. The seam is one: a second family that exists only here — a family module,
   a configuration that names it, a rehearsal cell, a metric file and its name
   in the configuration's `per_layer`, all NEW files under a temporary root —
   runs through `benchmark.run` on the CPU and ends `correct`, with that
   family's reference built, that family's account of a token's bytes
   compared and the configuration's own metric on the `--trace 1` result
   line; with one weight of its reference perturbed it ends `correct` false.
   No file that was there is touched.
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import configs
from benchmark.families import dense

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


# recorded on the parent commit (PR 25) from `reference.make_weights` and
# `reference.Reference(configs.model_kwargs(tiny-l2), seed)`: the leaves
# widened to float32, 128 rows of logits over 256 seeded tokens, and the
# same from int8 weights
RECORDED = {
    5: {"leaves": {
        "attn_norm": "02722f124d0f1736", "embed": "826b7c5257611100",
        "final_norm": "2f20cd03c9cd392a", "lm_head": "87d5396a61cd0e2b",
        "mlp_norm": "02722f124d0f1736", "w_down": "0f31510a8175a17d",
        "w_gate": "0c98971ff1b7582e", "w_up": "dcbea0628f96819a",
        "wk": "09b0cc18f9d582f1", "wo": "e13b9cc62145842d",
        "wq": "6207d6caa24a49e8", "wv": "e39bb4137fa856e5"},
        "logits": "5bd69814906d84a0", "int8_logits": "b3cd8e50e1ee4f73"},
    2 ** 31 + 11: {"leaves": {
        "attn_norm": "02722f124d0f1736", "embed": "0196d2bde9e16218",
        "final_norm": "2f20cd03c9cd392a", "lm_head": "56835e97c8eed392",
        "mlp_norm": "02722f124d0f1736", "w_down": "b102f00bee4093ea",
        "w_gate": "2e789a5d8abbd613", "w_up": "793985fa0a1fbc8d",
        "wk": "fdc84895a81ae4b1", "wo": "62a2ded99c99b9ca",
        "wq": "54680b260e0ee815", "wv": "671628dc337c7569"},
        "logits": "3238def04ed001c4", "int8_logits": "c7a51b6b352a9e33"},
}


@pytest.mark.parametrize("seed", sorted(RECORDED))
def test_the_dense_family_is_the_code_that_was_there_moved(seed):
    want = RECORDED[seed]
    ref = dense.Reference(configs.load_config("tiny-l2"), seed)
    assert {k: sha(v.astype("float32"))
            for k, v in ref.w.items()} == want["leaves"]
    tokens = np.random.default_rng(5).integers(3, 512, 256).astype(np.int32)
    rows = np.arange(0, 256, 2)
    assert sha(ref.logits(tokens, rows)) == want["logits"]
    ref.lower_to_int8()
    assert sha(ref.logits(tokens, rows)) == want["int8_logits"]


def test_the_dense_reference_imports_nothing_of_the_program():
    code = ("import sys; from benchmark import configs; "
            "raw = configs.load_config('tiny-l2'); "
            "configs.family(raw).Reference(raw, 1); "
            "sys.exit(any(m.split('.')[0] == 'quoracle_tpu' "
            "for m in sys.modules))")
    assert subprocess.run(
        [sys.executable, "-c", code], cwd=os.path.dirname(BENCH),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300
    ).returncode == 0


def test_a_configuration_that_names_no_family_is_refused():
    raw = configs.load_config("tiny-l2")
    assert configs.family(raw) is dense
    del raw["family"]
    with pytest.raises(KeyError, match="names no family"):
        configs.family(raw)


# -- the second family ------------------------------------------------------

TOY_FAMILY = '''
"""A family of the test's own: the dense toy under key names of its own, so
that the dense mapping could not read its file."""
from benchmark.families import dense

ASKED = []                  # what a run asked of THIS module
FAULT = {fault!r}             # a leaf of the reference to negate


def _published(raw):
    return {{**raw, "hidden_size": raw["width"],
            "num_hidden_layers": raw["depth"],
            "num_attention_heads": raw["heads"],
            "num_key_value_heads": raw["kv_heads"],
            "intermediate_size": raw["ffn_width"],
            "vocab_size": raw["tokens"]}}


def register(raw):
    ASKED.append("register")
    return dense.register(_published(raw))


class Reference(dense.Reference):
    def __init__(self, raw, seed):
        super().__init__(_published(raw), seed)
        ASKED.append("Reference")
        if FAULT:
            self.w[FAULT] = -self.w[FAULT]


def stated_precision(raw):
    ASKED.append("stated_precision")
    n = dense.stated_precision(_published(raw))["kv_bytes_per_token"]
    # two of the engine's keys, the second asked for by this family alone
    return {{"kv_bytes_per_token": n, "kv_bytes_per_token_bf16": n}}


def decode_weight_bytes(raw):
    return dense.decode_weight_bytes(_published(raw))


def decode_step_mark(raw):
    return {{"op_pattern": "^%toy_attend", "per_step": raw["depth"]}}
'''

TOY_CONFIG = {
    "source": "none: a family that exists only in benchmark/tests",
    "family": "toy", "width": 64, "depth": 2, "heads": 4, "kv_heads": 2,
    "ffn_width": 128, "tokens": 512, "bos_token_id": 1, "eos_token_id": 2,
    "hidden_act": "silu", "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "sliding_window": 1024,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "attention_bias": False, "serving": {"output_limit": 512},
    "control": {"precision": "the reference lowered to int8 weights",
                "serve_args": []},
    "per_layer": ["toy.rows_per_step"],
    "chips": 1, "serve_args": [],
    "env": {"QUORACLE_PAGED_CALIB": "benchmark/rehearsal_gates.json"},
}


def write_root(root, fault=None) -> str:
    """The new files, and nothing else, under `root`."""
    def put(rel, text):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    put("families/toy.py", TOY_FAMILY.format(fault=fault))
    put("configs/toy.json", json.dumps(TOY_CONFIG))
    put("cells_rehearsal.json", json.dumps({"workloads": [
        {"name": "toy.tiny-shots", "config": "toy", "traffic": "tiny-shots",
         "chips": 1}]}))
    put("metrics/toy.rows_per_step.json", json.dumps({
        "name": "toy.rows_per_step", "unit": "rows", "better": "higher",
        "layer": "backend and batcher", "source": "program_counter",
        "moves": "output_tokens_per_s", "reader": "rows_per_step"}))
    with open(os.path.join(BENCH, "warm", "tiny-l2.tiny-shots.json")) as f:
        put("warm/toy.tiny-shots.json", f.read())      # the same toy sizes
    return str(root)


def files_that_were_there() -> dict:
    out = {}
    for d, _, names in os.walk(BENCH):
        if "__pycache__" in d:
            continue
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                out[os.path.join(d, n)] = hashlib.sha256(f.read()).digest()
    return out


def run_toy(capsys, root: str, seed: int, trace: int) -> tuple:
    """(the result line, the `[tag]` lines before it by tag, stderr)."""
    from benchmark import run
    rc = run.main(["--workload", "toy.tiny-shots", "--seed", str(seed),
                   "--seconds", "4", "--trace", str(trace)], root=root)
    assert rc == 0
    said = capsys.readouterr()
    lines = said.out.strip().splitlines()
    tagged: dict = {}
    for ln in lines[:-1]:
        if ln.startswith("["):
            tagged.setdefault(ln[1:ln.index("]")], []).append(
                ln[ln.index("]") + 2:])
    return json.loads(lines[-1]), tagged, said.err


def test_a_second_family_is_new_files_alone(capsys, tmp_path):
    before = files_that_were_there()
    root = write_root(tmp_path / "sound")
    raw = configs.load_config("toy", root)
    toy = configs.family(raw, root)
    assert toy is not dense and toy.ASKED == []
    with pytest.raises(KeyError):            # the dense mapping cannot read it
        dense.model_kwargs(raw)
    line, tagged, err = run_toy(capsys, root, 2 ** 31 + 21, trace=1)
    assert line["correct"] is True and line["attempted"] > 0
    assert {"register", "Reference", "stated_precision"} <= set(toy.ASKED)
    # the family's account of a resident token's bytes is what was compared
    assert line["checks"]["kv_bytes_per_token_bf16"] == {
        "value": 256, "limit": 256}
    assert line["checks"]["reference_rows_compared"]["value"] > 0
    # the configuration's own metric, after its mix's
    assert line["metrics"]["toy.rows_per_step"]["unit"] == "rows"
    assert list(line["metrics"])[-1] == "toy.rows_per_step"
    assert "batcher.rows_per_step" in line["metrics"]
    assert files_that_were_there() == before
    # the one runner says what the second one said, on lines of their own:
    # what the profiler session cost the worker, what dropping sessions cost
    (tracing,) = [json.loads(t) for t in tagged["tracing"]]
    assert set(tracing) == {"session_open", "right_after"}
    assert tracing["session_open"]["device_wait_ms"] > 0
    (drops,) = [json.loads(t) for t in tagged["drops"]]
    assert drops["calls"] > 0 and drops["max_ms"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"}
    # every number compared beside its limit: last in the result line, and
    # the last lines of stderr, as on the `[check]` lines of stdout
    assert list(line)[-1] == "checks"
    on_stderr = [json.loads(ln[len("[check] "):])
                 for ln in err.strip().splitlines()[-len(line["checks"]):]]
    assert on_stderr == [json.loads(t) for t in tagged["check"]]
    assert {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in on_stderr} == line["checks"]
    assert all(c["passed"] for c in on_stderr)


def test_a_second_family_with_its_reference_perturbed_is_not_correct(
        capsys, tmp_path):
    root = write_root(tmp_path / "perturbed", fault="lm_head")
    line, _, _ = run_toy(capsys, root, 2 ** 31 + 22, trace=0)
    assert line["correct"] is False and line["failed"] == 0
    gap = line["checks"]["reference_gap"]
    assert gap["value"] > gap["limit"]
    # and nothing else failed: the program under it was sound
    assert line["checks"]["kv_bytes_per_token"] == {"value": 256,
                                                    "limit": 256}
