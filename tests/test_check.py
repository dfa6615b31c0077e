"""repro.check behavior: clean entries pass, every seeded rule fixture
fails, route prediction matches the runtime, and the CLI round-trips."""

import importlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.check import Report, Severity, run_check
from repro.check.__main__ import main as check_main
from repro.check.fixtures import FIXTURES
from repro.check.rules import all_rules, run_rules
from repro.core import nmg
from repro.core.layouts import CsrTensor, DenseTensor, GroupedNMTensor

kops = importlib.import_module("repro.kernels.ops")
disp = importlib.import_module("repro.core.dispatch")


# ---------------------------------------------------------------------------
# rule fixtures: trigger fails, clean passes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_trigger_fixture_fails_strict(rule_id):
    prog = FIXTURES[rule_id]["trigger"]()
    report = Report(run_rules(prog, rules=[rule_id]))
    hits = [d for d in report.diagnostics if d.rule == rule_id]
    assert hits, f"{rule_id} trigger fixture produced no {rule_id} diagnostic"
    assert report.exit_code(strict=True) != 0
    # severity matches the registry, and the diagnostic is fully typed
    rule = all_rules()[rule_id]
    for d in hits:
        assert d.severity == rule.severity
        assert d.entry and d.message
        assert d.rule == rule_id


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_clean_fixture_passes(rule_id):
    prog = FIXTURES[rule_id]["clean"]()
    assert not [d for d in run_rules(prog) if d.rule == rule_id], (
        f"{rule_id} clean fixture still trips {rule_id}"
    )


def test_error_rules_fail_even_without_strict():
    prog = FIXTURES["R1"]["trigger"]()
    report = Report(run_rules(prog, rules=["R1"]))
    assert report.exit_code(strict=False) != 0


def test_warning_rules_fail_only_under_strict():
    prog = FIXTURES["R2"]["trigger"]()
    report = Report(run_rules(prog, rules=["R2"]))
    assert report.exit_code(strict=False) == 0
    assert report.exit_code(strict=True) != 0


def test_ignore_suppresses_rule():
    prog = FIXTURES["R2"]["trigger"]()
    report = Report(run_rules(prog, rules=["R2"]))
    assert report.filtered(["R2"]).exit_code(strict=True) == 0
    # entry-scoped suppression only hits matching entries
    assert report.filtered(["R2:nomatch-*"]).exit_code(strict=True) != 0
    assert report.filtered(["R2:fixture/*"]).exit_code(strict=True) == 0


# ---------------------------------------------------------------------------
# real entries: the clean repo passes
# ---------------------------------------------------------------------------


def test_serve_entry_clean():
    report = run_check(("serve",), arch="bert-base-sten", hlo=False)
    assert report.render() == ""
    assert report.exit_code(strict=True) == 0
    assert any(":decode" in p for p in report.programs)
    assert any(":prefill" in p for p in report.programs)


def test_train_entry_clean():
    report = run_check(("train",), arch="bert-base-sten", hlo=False)
    assert report.exit_code(strict=True) == 0


def test_cli_json_report(tmp_path):
    out = tmp_path / "report.json"
    rc = check_main(["--entry", "decode", "--no-hlo", "--json", str(out),
                     "--strict"])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["errors"] == 0
    assert doc["programs"]
    assert isinstance(doc["diagnostics"], list)


# ---------------------------------------------------------------------------
# predict_route: dispatch level
# ---------------------------------------------------------------------------


def _gnm(R=8, K=96, fmt=(1, 4, 4), gr=2):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    n, m, g = fmt
    return nmg.dense_to_grouped_nm(x, n=n, m=m, g=g, gr=gr)


def test_dispatch_predict_route_impl():
    got = disp.predict_route("linear", (DenseTensor, GroupedNMTensor))
    assert got["outcome"] == "impl"
    assert got["sig"] == ("DenseTensor", "GroupedNMTensor")
    assert got["conversions"] == ()


def test_dispatch_predict_route_conversion():
    from repro.core.layouts import CooTensor

    got = disp.predict_route("matmul", (CooTensor, DenseTensor))
    assert got["outcome"] == "impl"
    assert got["conversions"] == (("CooTensor", "CsrTensor"),)
    assert got["target_sig"] == ("CsrTensor", "DenseTensor")


def test_dispatch_predict_route_fallback_and_no_counter_pollution():
    before = disp.dispatch_counters()
    got = disp.predict_route("definitely_not_registered",
                             (CsrTensor, DenseTensor))
    assert got["outcome"] == "dense_fallback"
    assert got["warns"] is True
    # prediction is side-effect-free: counters unchanged
    assert disp.dispatch_counters() == before


def test_dispatch_predict_route_accepts_instances():
    t = _gnm()
    got = disp.predict_route("linear", (jnp.ones((4, 96)), t))
    assert got["sig"] == ("DenseTensor", "GroupedNMTensor")


# ---------------------------------------------------------------------------
# predict_route: kernel level, cross-checked against the real router
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M", [2, 64])
def test_kernels_predict_route_matches_runtime(M):
    t = _gnm()
    predicted = set(map(tuple, kops.predict_route(
        "nmg_linear", t, M=M, dtype=jnp.float32, use_pallas=False)))
    kops.reset_kernel_counters()
    kops.nmg_linear(jnp.ones((M, 96), jnp.float32), t, use_pallas=False)
    observed = set(kops.kernel_counters())
    assert predicted == observed


def test_kernels_predict_route_is_table_sensitive():
    from repro.tune.routing import set_active_table
    from repro.tune.table import TuningTable, device_kind

    t = _gnm()
    # crossover forced below M=4: the same call flips gemv -> spmm
    tab = TuningTable(device=device_kind(), entries={"decode_m_max": 2})
    set_active_table(tab)
    keys = kops.predict_route("nmg_linear", t, M=4, dtype=jnp.float32,
                              use_pallas=False)
    assert ("nmg_linear", "spmm[table]") in keys


def test_kernels_predict_route_unknown_op():
    with pytest.raises(ValueError, match="unknown op"):
        kops.predict_route("nope", _gnm(), M=4, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# differential mode: static prediction vs the live engine's counters
# ---------------------------------------------------------------------------


def test_differential_static_vs_runtime_agree():
    from repro.check.differential import differential_check

    diags, detail = differential_check()
    assert detail["agree"], "\n".join(d.render() for d in diags)
    assert detail["predicted"] == detail["observed"]
    # the quick warmup straddles the gemv/spmm crossover, so both routed
    # paths are part of the comparison surface
    assert any("gemv" in k for k in detail["observed"])
    assert any("spmm" in k for k in detail["observed"])


# ---------------------------------------------------------------------------
# table-load provenance reaches the checker's world
# ---------------------------------------------------------------------------


def test_vmem_estimates_carry_table_provenance():
    from repro.check.program import build_program
    from repro.tune.routing import clear_active_table, set_active_table
    from repro.tune.table import TuningTable, device_kind

    t = _gnm()
    tab = TuningTable(device=device_kind(),
                      entries={"gemv_pallas": {"tm": 8, "target_depth": 64}})
    set_active_table(tab)
    try:
        prog = build_program("t/prov", lambda x: x, (jnp.ones((2, 96)),),
                             model_dtype=jnp.float32,
                             sparse_weights={"w": t}, decode_m=2)
    finally:
        clear_active_table()
    (est,) = prog.vmem_estimates
    assert est["source"] == "table"
    assert est["config"]["tm"] == 8
    assert est["bytes"] <= est["budget"]


def test_unmodelled_device_with_sparse_weights_reports_r7():
    # a device with no HW entry has no VMEM budget: the checker reports R7
    # instead of estimating against another chip's numbers (or crashing)
    from repro.check.program import build_program

    prog = build_program("t/r7", lambda x: x, (jnp.ones((2, 96)),),
                         model_dtype=jnp.float32,
                         sparse_weights={"w": _gnm()}, decode_m=2,
                         device_kind="tpu:tpu_v99")
    assert prog.vmem_estimates == []
    assert [d.rule for d in run_rules(prog)] == ["R7"]


def test_hw_for_device_refuses_unknown_kind():
    from repro.launch.hlo_analysis import hw_for_device

    v5e = hw_for_device("tpu:tpu_v5_lite")   # what a v5e reports
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no entry"):
        hw_for_device("tpu:tpu_v5e")
