"""Chip-economics plane (infra/costobs.py, ISSUE 17).

The plane's acceptance bar:

  * attribution EXACTNESS — per-stage cell sums equal the stage wall
    and the engine busy wall in integer nanoseconds, never "within
    tolerance" (padding/remainder waste lands on the ``overhead``
    pseudo-tenant, not on rows and not on the floor);
  * read-only — temp-0 output is BIT-IDENTICAL with accounting on and
    off, across greedy, grammar-constrained, and speculative decode on
    both a monolithic backend and the continuous scheduler path;
  * budget determinism — identical (tenant, cls, ok, t) sequences
    reproduce identical burn rates and sha256 trip ids (chaos-plane
    rules: no wall clock in any decision);
  * calibration closes the loop — a CapacityModel fitted from a
    recorded ledger (sim/calibrate.py) replays the trace with the
    measured TTFT distribution inside the gate tolerance.
"""

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.infra import costobs
from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

MEMBER = "xla:tiny"
K_A = ("tenant-a", "interactive", "t1", "d1")
K_B = ("tenant-b", "agent", "t2", "d2")


@pytest.fixture(autouse=True)
def _clean_plane():
    costobs.reset()
    costobs.enable()
    yield
    costobs.reset()
    costobs.enable()


def make_engine(**kw):
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def stage_cell_sums(led):
    out = {}
    for key, ns in led.cells().items():
        out[key[4]] = out.get(key[4], 0) + ns
    return out


# ---------------------------------------------------------------------------
# Attribution arithmetic: exact by construction
# ---------------------------------------------------------------------------

def test_charge_sum_invariant_exact():
    """sum(cells of stage S) == stage_ns[S]; sum(stage walls) == busy —
    integer equality, across ragged weights, padding, and zero rows."""
    led = costobs.ChipLedger("t")
    led.charge("prefill", 0.0123457, [7, 13, 1], [K_A, K_B, K_A], 64)
    led.charge("decode", 0.0031415, [5, 0, 9], [K_A, K_B, K_A], 32)
    led.charge("verify", 0.0000019, [3], [K_B], 3)
    led.charge("restore", 0.0400001, [1], [costobs.DEFAULT_KEY], 1)
    assert stage_cell_sums(led) == led.stage_ns()
    assert sum(led.stage_ns().values()) == led.busy_ns()
    # all-zero weights: the whole wall is overhead, still conserved
    led.charge("decode", 0.002, [0, 0], [K_A, K_B], 8)
    assert stage_cell_sums(led) == led.stage_ns()
    assert sum(led.stage_ns().values()) == led.busy_ns()


def test_padding_waste_lands_on_overhead_tenant():
    led = costobs.ChipLedger("t")
    shares = led.charge("prefill", 0.010, [3, 5], [K_A, K_B], 16)
    # 8 real tokens of 16 slots: half the wall is padding overhead
    assert sum(shares) == 5_000_000
    snap = led.snapshot()
    assert snap["overhead_chip_ms"] == 5.0
    assert snap["by_tenant_chip_ms"]["tenant-a"] == pytest.approx(1.875)
    assert snap["by_stage_tokens"] == {"prefill": 8}


def test_row_key_context_mismatch_degrades_to_default():
    """A missing or mis-sized thread-local declaration must not lose
    the charge — it lands on DEFAULT_KEY and the sums stay exact."""
    costobs.set_row_keys([K_A])           # wrong length for n=2
    keys = costobs._take_row_keys(2)
    assert keys == [costobs.DEFAULT_KEY] * 2
    assert costobs._take_row_keys(1) == [costobs.DEFAULT_KEY]  # cleared


def test_key_of_reads_rows_and_dicts():
    assert costobs.key_of({"tenant": "t", "priority": "agent",
                           "task_id": "x", "decide": "d"}) == \
        ("t", "agent", "x", "d")

    class Row:
        tenant, priority, task_id, decide = "u", 0, None, "d9"
    assert costobs.key_of(Row()) == ("u", "-", "-", "d9")


# ---------------------------------------------------------------------------
# Read-only: temp-0 bit-equality with accounting on/off
# ---------------------------------------------------------------------------

def test_engine_temp0_bit_equal_accounting_on_off():
    """Greedy + constrained JSON through the raw engine: accounting on
    vs off must be BIT-identical, and on-mode rows carry chip-ms."""
    eng = make_engine()
    p = enc("user: tell me about chip accounting")
    on_g = eng.generate([p], temperature=0.0, max_new_tokens=24)[0]
    on_c = eng.generate([p], temperature=0.0, max_new_tokens=32,
                        constrain_json=[True])[0]
    assert on_g.chip_ms > 0.0
    # the ledger keys by cfg.name — the same label kvtier/telemetry use
    assert costobs.ledger_for(eng.cfg.name).busy_ns() > 0
    costobs.disable()
    off_g = eng.generate([p], temperature=0.0, max_new_tokens=24)[0]
    off_c = eng.generate([p], temperature=0.0, max_new_tokens=32,
                         constrain_json=[True])[0]
    assert off_g.token_ids == on_g.token_ids
    assert off_g.text == on_g.text
    assert off_c.token_ids == on_c.token_ids
    assert off_g.chip_ms == 0.0


def test_speculative_temp0_bit_equal_accounting_on_off(request):
    from quoracle_tpu.models.speculative import SpeculativeDecoder
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = SpeculativeDecoder(cfg, params, cfg, params, ByteTokenizer(),
                              k=4, max_seq=256,
                              cache_dtype=jnp.float32)
    p = enc("user: speculative accounting test")
    on = spec.generate(p, temperature=0.0, max_new_tokens=24)
    costobs.disable()
    off = spec.generate(p, temperature=0.0, max_new_tokens=24)
    assert off.token_ids == on.token_ids
    assert off.finish_reason == on.finish_reason


def test_backend_scheduler_temp0_bit_equal_and_attributed():
    """The production path (TPUBackend + continuous scheduler): on/off
    bit-equality, chip-ms on the QueryResult, and the ledger's cells
    keyed by the submitted tenant / task / decide."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    b = TPUBackend([MEMBER], continuous_chunk=8)
    try:
        def q():
            return b.query([QueryRequest(
                MEMBER, [{"role": "user", "content":
                          "hello economics plane"}],
                temperature=0.0, max_tokens=20, tenant="acme",
                priority=0, task_id="task-7", decide="d-42")])[0]
        on = q()
        assert on.ok, on.error
        assert on.chip_ms > 0.0
        led = costobs.ledger_for(b.engines[MEMBER].cfg.name)
        assert stage_cell_sums(led) == led.stage_ns()
        assert sum(led.stage_ns().values()) == led.busy_ns()
        tenants = {k[0] for k in led.cells()}
        assert "acme" in tenants
        keyed = [k for k in led.cells() if k[0] == "acme"]
        assert all(k[1] == "interactive" and k[2] == "task-7"
                   and k[3] == "d-42" for k in keyed)
        costobs.disable()
        off = q()
        assert off.ok, off.error
        assert off.text == on.text
        assert off.chip_ms == 0.0
        assert led.busy_ns() == sum(led.stage_ns().values())
    finally:
        b.close()
        costobs.enable()


def test_cluster_temp0_bit_equal_accounting_on_off():
    """Disaggregated plane: the prefill→decode handoff path stays
    bit-identical with the plane on and off."""
    from quoracle_tpu.models.runtime import QueryRequest
    from quoracle_tpu.serving.cluster import ClusterPlane
    cl = ClusterPlane.build([MEMBER], replicas=2, disaggregate=True,
                            continuous_chunk=8)
    try:
        def q():
            return cl.query([QueryRequest(
                MEMBER, [{"role": "user", "content":
                          "cluster accounting parity"}],
                temperature=0.0, max_tokens=20, tenant="acme")])[0]
        on = q()
        assert on.ok, on.error
        costobs.disable()
        off = q()
        assert off.ok, off.error
        assert off.text == on.text
    finally:
        cl.close()
        costobs.enable()


# ---------------------------------------------------------------------------
# Roofline / MFU
# ---------------------------------------------------------------------------

def test_roofline_mfu_and_cliff_flight_event():
    from quoracle_tpu.infra.flightrec import FLIGHT
    eng = make_engine()
    rf = costobs.roofline_for(eng)
    assert rf is costobs.roofline_for(eng)     # cached on the engine
    obs = rf.observe("prefill", 64, 1, 64, 0.004, 64)
    assert obs is not None and 0.0 < obs["mfu"]
    assert rf.observe("prefill", 0, 1, 64, 0.004, 64) is None
    before = len([e for e in FLIGHT.snapshot()
                  if e["kind"] == "mfu_cliff"])
    # 10x the wall for the same work: > 2x MFU drop → one cliff trip
    rf.observe("prefill", 64, 1, 64, 0.040, 64)
    rf.observe("prefill", 64, 1, 64, 0.041, 64)   # stays low: no re-trip
    after = [e for e in FLIGHT.snapshot()
             if e["kind"] == "mfu_cliff"]
    assert len(after) == before + 1
    assert after[-1]["stage"] == "prefill"


# ---------------------------------------------------------------------------
# Error budgets: deterministic multi-window burn
# ---------------------------------------------------------------------------

def _feed(tracker, seq):
    for tenant, cls, ok, t in seq:
        tracker.record(tenant, cls, ok, t)


def test_budget_burn_trips_deterministically():
    seq = [("acme", "interactive", True, 10.0 + i) for i in range(40)]
    seq += [("acme", "interactive", False, 60.0 + i) for i in range(10)]
    a, b = costobs.BudgetTracker(), costobs.BudgetTracker()
    _feed(a, seq)
    _feed(b, seq)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa == sb                        # bit-identical replays
    ent = sa["tenants"]["acme"]["interactive"]
    # 10 errors / 50 events at a 99.9% SLO: burn 200x — both windows trip
    assert ent["windows"]["1h"]["burn"] == pytest.approx(200.0)
    assert ent["windows"]["1h"]["tripping"]
    assert ent["trips"] == {"1h": 1, "6h": 1}
    assert a.burn_signals() == b.burn_signals()
    assert a.burn_signals()["interactive"] == pytest.approx(200.0)


def test_budget_recovery_discards_trip_state():
    t = costobs.BudgetTracker()
    _feed(t, [("a", "batch", False, 1.0)])
    assert t.snapshot()["tenants"]["a"]["batch"]["windows"]["1h"][
        "tripping"]
    # a flood of successes inside the window drops burn below threshold
    _feed(t, [("a", "batch", True, 2.0 + i * 0.01) for i in range(400)])
    ent = t.snapshot()["tenants"]["a"]["batch"]
    assert not ent["windows"]["1h"]["tripping"]
    assert ent["trips"]["1h"] == 1         # history kept, state cleared


def test_budget_disabled_records_nothing():
    costobs.disable()
    costobs.BUDGET.record("x", "batch", ok=False, t=5.0)
    assert costobs.BUDGET.snapshot()["tenants"] == {}


# ---------------------------------------------------------------------------
# Payloads + observed signals
# ---------------------------------------------------------------------------

def test_costs_payload_shape():
    led = costobs.ledger_for("m1")
    led.charge("prefill", 0.004, [4], [K_A], 8)
    payload = costobs.costs_payload()
    assert payload["enabled"]
    assert payload["total_chip_ms"] == pytest.approx(4.0)
    assert payload["models"]["m1"]["by_stage_chip_ms"]["prefill"] == 4.0


def test_admission_signals_carry_budget_burn_observed_only():
    from quoracle_tpu.serving.admission import (
        AdmissionConfig, AdmissionController,
    )
    costobs.BUDGET.record("acme", "batch", ok=False, t=100.0)
    ctl = AdmissionController(AdmissionConfig())
    snap = ctl.signals()
    assert snap.budget_burn.get("batch", 0.0) > 0
    assert "budget_burn" in snap.as_dict()


# ---------------------------------------------------------------------------
# Sim calibration: the measured-profile loop closes
# ---------------------------------------------------------------------------

def test_calibration_recovers_profile_and_ttft_gate_passes():
    from quoracle_tpu.sim import calibrate as cal
    from quoracle_tpu.sim.replay import CapacityModel
    from quoracle_tpu.sim.workload import canonical_spec, generate
    trace = generate(canonical_spec("diurnal_mix"))
    truth = CapacityModel(prefill_tok_s=30_000.0, decode_tok_s=250.0)
    chip, measured = cal.record_profile(trace, truth)
    rep = cal.fit_capacity(chip)
    assert "prefill_tok_s" in rep.fitted_params
    assert rep.fitted.prefill_tok_s == pytest.approx(30_000.0, rel=0.02)
    assert rep.fitted.decode_tok_s == pytest.approx(250.0, rel=0.02)
    gate = cal.ttft_gate(trace, measured, rep.fitted, tol=0.35)
    assert gate["passed"], gate["checks"]
    # fitting twice is bit-identical (no clock, no RNG)
    assert cal.fit_capacity(chip).as_dict() == rep.as_dict()
    # the recording fixture never leaks into live ledgers
    assert "sim:profile" not in costobs.ledgers()


def test_calibration_fits_restore_rungs():
    from quoracle_tpu.sim.calibrate import fit_capacity
    led = costobs.ChipLedger("t")
    for _ in range(8):
        led.charge("restore", 0.012, [1], [costobs.DEFAULT_KEY], 1)
        led.note_restore_source("host", 12_000_000)
    rep = fit_capacity(led)
    assert "restore_ms:host" in rep.fitted_params
    assert dict(rep.fitted.restore_ms)["host"] == pytest.approx(12.0)
    # unseen rungs keep the base penalty
    assert dict(rep.fitted.restore_ms)["disk"] == 40


def test_calibrate_from_live_ledgers_picks_busiest():
    from quoracle_tpu.sim.calibrate import calibrate
    assert calibrate() is None             # nothing charged yet
    small = costobs.ledger_for("small")
    small.charge("prefill", 0.001, [40], [K_A], 40)
    big = costobs.ledger_for("big")
    big.charge("prefill", 0.004, [400], [K_A], 400)
    rep = calibrate()
    assert rep.model == "big"
    assert calibrate(model="small").model == "small"
