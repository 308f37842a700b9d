"""The tests that keep `correct` honest, at a size a test run can hold.

1. The control of the comparison: the program's own int8 weights (the path
   that cannot start at the cells' widths on one chip) serve greedy rows, and
   the comparison with the plain reference comes out over the limit, while
   the sound program stays under it.
2. The control the chip runs, `benchmark.control`: a whole run of a rehearsal
   cell with the program's int8 KV pages switched on ends with `correct`
   false — by the precision the engine reports, since the comparison of
   served tokens cannot tell such pages from bfloat16's own rounding.
3. A whole run of the harness with the timed path broken underneath (the
   sampler alters every token where it is produced; a program compiles
   inside the window; a warm tick misses its key) ends with `correct`
   false — and the same run, sound, with `correct` true.
"""

import json

import jax
import numpy as np
import pytest

from benchmark import configs
from benchmark.families import dense
from benchmark.reference import gaps_of as gaps
from benchmark.reference import served_logits


def gaps_of(seed: int, quantize: bool) -> np.ndarray:
    from quoracle_tpu.models.config import ModelConfig
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    raw = configs.load_config("tiny-l2")
    cfg = ModelConfig(**dense.model_kwargs(raw))
    eng = GenerateEngine(cfg, init_params(cfg, jax.random.PRNGKey(seed)),
                         ByteTokenizer(), seed=seed,
                         quantize_weights=quantize)
    eng.unified_min_tokens = 0
    ref = dense.Reference(raw, seed)
    rng = np.random.default_rng(seed)
    out = []
    for r in range(8):
        prompt = [int(x) for x in rng.integers(3, 259, 150 + 40 * r)]
        eng.generate([prompt], temperature=0.0, max_new_tokens=64,
                     session_ids=[f"s{r}"])
        ids = eng.session_tokens(f"s{r}")
        out.append(gaps(served_logits(ref, ids, len(prompt), 512),
                        np.asarray(ids[len(prompt):])))
    return np.concatenate(out)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_int8_weights_fail_the_limit_the_sound_program_passes(seed):
    # a limit on the mean gap set as PERF.md says for the real cells, from
    # readings at this size over five seeds and some 500 tokens each: sound
    # runs 0.00008-0.0002, int8 0.0009-0.0018
    limit = 0.0005
    sound, control = gaps_of(seed, False), gaps_of(seed, True)
    assert sound.mean() <= limit
    assert control.mean() > limit


def run_cell(capsys, seed: int) -> dict:
    from benchmark import run
    rc = run.main(["--workload", "tiny-l2.tiny-shots", "--seed", str(seed),
                   "--seconds", "4", "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_programs_int8_kv_pages_are_not_correct(capsys):
    from benchmark import control
    rc = control.main(["--workload", "tiny-l2.tiny-shots", "--seed", "15",
                       "--seconds", "4"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0                     # the control came out as it should
    assert json.loads(lines[-2])["correct"] is False
    said = json.loads(lines[-1][len("[control] "):])
    assert said["serve_args"] == ["--quantize-kv"]
    assert said["checks"]["kv_bytes_per_token"]["passed"] is False
    assert said["checks"]["rows_failed"]["passed"] is True


def test_a_run_with_the_sampler_broken_is_not_correct(capsys, monkeypatch):
    from quoracle_tpu.models import generate
    sound = run_cell(capsys, 11)
    assert sound["correct"] is True and sound["attempted"] > 0
    assert sound["device"]["platform"] == "cpu"
    real = generate.sample_tokens

    def broken(logits, *a, **k):            # every token one id too high
        return (real(logits, *a, **k) + 1) % logits.shape[-1]
    monkeypatch.setattr(generate, "sample_tokens", broken)
    line = run_cell(capsys, 12)
    assert line["correct"] is False and line["failed"] == 0


def test_a_run_that_compiles_in_its_window_is_not_correct(capsys,
                                                         monkeypatch):
    """A program key that warm-up did not land on is compiled by the first
    request that needs it, inside the window: the run says so."""
    from benchmark import warmup
    monkeypatch.setattr(warmup.Warmer, "warm", lambda self, keys: {
        "wanted": [list(k) for k in keys], "extra": [], "missed": []})
    line = run_cell(capsys, 13)
    assert line["correct"] is False and line["failed"] == 0


def test_a_warm_tick_that_misses_its_key_is_not_correct(capsys, monkeypatch):
    """The program changed its buckets: the cell's keys no longer land."""
    from benchmark import warmup
    real = warmup.Warmer.warm

    def warm(self, keys):
        return {**real(self, keys), "missed": [[4096, 64]]}
    monkeypatch.setattr(warmup.Warmer, "warm", warm)
    line = run_cell(capsys, 14)
    assert line["correct"] is False and line["failed"] == 0


def test_a_benchmark_cell_off_the_tpu_exits_non_zero(capsys):
    from benchmark import run
    rc = run.main(["--workload", "mistral-7b-l16.agent-turns", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and not out.out.strip()


def test_the_reference_lowered_to_int8_puts_other_tokens_first():
    """The control of the chip runs, at toy size: the same model from int8
    weights disagrees with the float32 reference about the best token often
    enough to read, and by margins no rounding of bfloat16 reaches."""
    ref = dense.Reference(configs.load_config("tiny-l2"), 5)
    tokens = np.random.default_rng(5).integers(3, 512, 512).astype(np.int32)
    rows = np.arange(512)
    sound = ref.logits(tokens, rows)
    ref.lower_to_int8()
    low = ref.logits(tokens, rows)
    assert np.abs(low - sound).max() < 0.5          # the same model, still
    g = gaps(sound, low.argmax(-1))
    assert (g > 0).sum() >= 5 and g.mean() > 0.0005


@pytest.mark.parametrize("sound,serve_args,correct,lowered_passes,want", [
    (True, ["--quantize-kv"], True, False, True),     # --sound: correct
    (True, [], False, False, False),
    (False, ["--quantize-kv"], False, None, True),    # the program's path
    (False, ["--quantize-kv"], True, False, False),
    (False, [], True, False, True),     # no path: the lowered reference
    (False, [], True, True, False),     # ... which passed both limits
    (False, [], False, False, False),   # ... or the sound run was not sound
    (False, [], True, None, False),     # ... or no row was compared
])
def test_what_a_control_run_has_to_come_out_as(sound, serve_args, correct,
                                               lowered_passes, want):
    """`control.serve_args` empty is what a family whose program has no
    lower-precision path states: then the reference lowered to int8, in
    the program's place, has to fail a limit the sound run passes."""
    from benchmark import control
    assert control.as_it_should(sound, serve_args, correct,
                                lowered_passes) is want
