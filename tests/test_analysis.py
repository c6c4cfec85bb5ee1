"""The static verification layer's own tests.

Three kinds of coverage:

  * the linter catches what it claims to catch — every known-bad fixture
    in tests/fixtures/analysis/ is flagged by EXACTLY its rule, synthetic
    bad HLO text trips each HLO check, and injected violations in real
    lowered programs (an all_gather smuggled into a shard_map) are found;
  * the escape hatches and declarations are load-bearing — noqa lines
    suppress, deleting a @contract is a finding, unknown invariant names
    are findings, manifest rot (a lane dict that stops parsing as a
    ServeConfig) is a finding;
  * the shipped codebase is CLEAN — the AST and async passes over src/,
    the host-side contract harnesses in-process, and the full five-pass
    CLI in a subprocess (which is also the < 120 s budget check, on a
    small grid);
  * the cost gates judge correctly — pure exponent-fit/budget/baseline
    checks on synthetic records in-process, plus REAL compiled injections
    (a replicated cache in the sharded in_specs, a pairwise q_max^2 term)
    in a subprocess, and the CLI baseline-drift / --update-baselines
    round trip.

Mesh-requiring checks (HLO lowering, sharded contracts) run via the CLI
subprocess: the analysis front door forces virtual host devices before
jax initializes, which an already-initialized pytest process cannot.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import Finding, astlint, asynclint, contracts, costs, hlo
from repro.analysis import invariants as inv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


def _fixture(rel):
    path = os.path.join(FIXTURES, rel)
    with open(path, encoding="utf-8") as f:
        return path, f.read()


# --------------------------------------------------------------------------
# Finding / manifest basics
# --------------------------------------------------------------------------


def test_finding_validates():
    with pytest.raises(ValueError):
        Finding("nonsense-pass", "R", "w", "m")
    with pytest.raises(ValueError):
        Finding("ast", "", "w", "m")
    f = Finding("ast", "RR001", "a.py:3", "boom")
    assert f.to_dict()["rule"] == "RR001" and "a.py:3" in str(f)


def test_lane_manifest_is_valid_serve_configs():
    from repro.api.config import ServeConfig

    assert len(inv.LANES) == 14
    names = [l.name for l in inv.LANES]
    assert len(set(names)) == len(names)
    for lane in inv.LANES:
        cfg = ServeConfig.from_dict(lane.serve)  # manifest rot -> raises
        assert cfg.mode in ("replicated", "sharded")
    # exactly 4 distinct device programs behind the 14 lanes
    assert len({l.program_key for l in inv.LANES}) == 4


def test_lane_invariant_rejects_bad_declarations():
    with pytest.raises(ValueError):
        inv.LaneInvariant(
            name="x", serve={}, program="warp-drive", backend="ref",
            max_collective_permute=0, forbidden_ops=(),
        )
    with pytest.raises(ValueError):
        inv.LaneInvariant(
            name="x", serve={}, program="sharded-blend", backend="ref",
            max_collective_permute=2, min_collective_permute=4,
            forbidden_ops=(),
        )
    with pytest.raises(ValueError):
        inv.LaneInvariant(
            name="x", serve={}, program="sharded-blend", backend="ref",
            max_collective_permute=8, forbidden_ops=("warp-gather",),
        )


# --------------------------------------------------------------------------
# HLO pass: text checks on synthetic programs (no jax needed)
# --------------------------------------------------------------------------

SHARDED_LANE = next(l for l in inv.LANES if l.program == "sharded-blend")
REPLICATED_LANE = next(l for l in inv.LANES if l.program == "replicated-blend")

# a minimal halo-shaped program: 4 ppermutes, f32 only
GOOD_TEXT = "\n".join(
    f'%r{i} = "stablehlo.collective_permute"(%a) : tensor<9x64xf32>'
    for i in range(4)
)


def _rules(findings):
    return sorted({f.rule for f in findings})


def test_hlo_good_text_is_clean():
    findings, counts = hlo.check_text(SHARDED_LANE, GOOD_TEXT)
    assert findings == [] and counts["collective-permute"] == 4


@pytest.mark.parametrize(
    "mutation,rule",
    [
        # a gathering collective in a sharded program
        ('%g = "stablehlo.all_gather"(%a) : tensor<16x8xf32>', "HLO-FORBIDDEN-OP"),
        # HLO (dashed) spelling must be caught too
        ("%g = all-gather(%a)", "HLO-FORBIDDEN-OP"),
        ("%g = all-reduce-start(%a)", "HLO-FORBIDDEN-OP"),
        # an f64 leak
        ("%c = stablehlo.constant dense<0.5> : tensor<64xf64>", "HLO-DTYPE-F64"),
        ("%c = f64[9,64] constant(...)", "HLO-DTYPE-F64"),
        # a host transfer inside the compiled program
        ('%h = "stablehlo.infeed"(%tok)', "HLO-HOST-TRANSFER"),
        ("%h = xla_python_cpu_callback(%a)", "HLO-HOST-TRANSFER"),
    ],
)
def test_hlo_bad_text_caught_by_exactly_the_expected_rule(mutation, rule):
    findings, _ = hlo.check_text(SHARDED_LANE, GOOD_TEXT + "\n" + mutation)
    assert _rules(findings) == [rule], findings


def test_hlo_budget_and_floor():
    over = GOOD_TEXT + "\n" + "\n".join(
        f'%e{i} = "stablehlo.collective_permute"(%a)' for i in range(9)
    )
    findings, counts = hlo.check_text(SHARDED_LANE, over)
    assert _rules(findings) == ["HLO-COLLECTIVE-BUDGET"] and counts[
        "collective-permute"
    ] == 13
    # the floor: a sharded program whose halo vanished is wrong too
    findings, _ = hlo.check_text(SHARDED_LANE, "%z = stablehlo.add(%a, %b)")
    assert _rules(findings) == ["HLO-COLLECTIVE-MISSING"]


def test_hlo_replicated_lane_forbids_all_collectives():
    findings, _ = hlo.check_text(REPLICATED_LANE, GOOD_TEXT)
    assert "HLO-COLLECTIVE-BUDGET" in _rules(findings)
    findings, _ = hlo.check_text(
        REPLICATED_LANE, '%r = "stablehlo.all_reduce"(%a)'
    )
    assert "HLO-FORBIDDEN-OP" in _rules(findings)


def test_hlo_manifest_rot_is_a_finding():
    rotten = inv.LaneInvariant(
        name="rotten", serve={"mode": "sharded", "warp_factor": 9},
        program="sharded-blend", backend="ref",
        max_collective_permute=8, forbidden_ops=(),
    )
    findings, report = hlo.run(lanes=(rotten,))
    assert _rules(findings) == ["HLO-MANIFEST"]
    assert report["lanes"] == []  # never lowered


# --------------------------------------------------------------------------
# AST pass: fixtures each caught by exactly the expected rule
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rel,rule",
    [
        ("bad_import_time.py", "RR001"),
        (os.path.join("repro", "core", "routing.py"), "RR002"),
        (os.path.join("repro", "kernels", "bad_f64.py"), "RR003"),
        ("bad_config.py", "RR004"),
    ],
)
def test_fixture_caught_by_exactly_the_expected_rule(rel, rule):
    path, source = _fixture(rel)
    findings = astlint.lint_source(path, source)
    assert findings, f"{rel}: nothing caught"
    assert _rules(findings) == [rule], findings


def test_noqa_suppresses():
    path, source = _fixture("suppressed_ok.py")
    assert astlint.lint_source(path, source) == []
    # and removing the noqa markers brings the findings back
    stripped = "\n".join(
        line.split("# repro: noqa-")[0] for line in source.splitlines()
    )
    assert _rules(astlint.lint_source(path, stripped)) == ["RR001", "RR004"]


def test_rr002_declared_function_cannot_silently_vanish():
    source = "import numpy as np\n"  # none of the declared functions exist
    findings = astlint.lint_source("src/repro/core/routing.py", source)
    assert findings and _rules(findings) == ["RR002"]
    assert any("not found" in f.message for f in findings)


def test_rr001_skips_lazy_contexts():
    source = textwrap.dedent(
        """
        import functools
        import jax
        import jax.numpy as jnp

        def f(x):
            return jnp.asarray(x)

        g = functools.partial(jax.jit, static_argnames=("k",))

        @jax.jit
        def h(x):
            return x
        """
    )
    assert astlint.lint_source("src/repro/x.py", source) == []


def test_rr001_catches_function_default_args():
    source = "import jax.numpy as jnp\ndef f(x=jnp.zeros(3)):\n    return x\n"
    assert _rules(astlint.lint_source("src/repro/x.py", source)) == ["RR001"]


def test_shipped_codebase_is_clean():
    findings, report = astlint.run(os.path.join(REPO, "src"))
    assert findings == [], [str(f) for f in findings]
    assert report["files_scanned"] > 60


def test_fixture_tree_is_dirty_end_to_end():
    findings, _ = astlint.run(FIXTURES)
    assert _rules(findings) == ["RR001", "RR002", "RR003", "RR004"]


# --------------------------------------------------------------------------
# Async pass: fixtures, escape hatch, confinement, shipped-clean
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rel,rule",
    [
        ("bad_async_rr005.py", "RR005"),
        ("bad_async_rr006.py", "RR006"),
        ("bad_async_net_rr006.py", "RR006"),
        ("bad_async_rr007.py", "RR007"),
        ("bad_async_rr008.py", "RR008"),
    ],
)
def test_async_fixture_caught_by_exactly_the_expected_rule(rel, rule):
    path, source = _fixture(rel)
    findings = asynclint.lint_source(path, source)
    assert findings, f"{rel}: nothing caught"
    assert _rules(findings) == [rule], findings


def test_async_noqa_suppresses():
    path, source = _fixture("suppressed_async_ok.py")
    assert asynclint.lint_source(path, source) == []
    stripped = "\n".join(
        line.split("# repro: noqa-")[0] for line in source.splitlines()
    )
    assert _rules(asynclint.lint_source(path, stripped)) == ["RR005", "RR007"]


def test_async_shipped_codebase_is_clean():
    findings, report = asynclint.run(os.path.join(REPO, "src"))
    assert findings == [], [str(f) for f in findings]
    assert report["files_scanned"] > 60


def test_async_fixture_tree_is_dirty_end_to_end():
    findings, _ = asynclint.run(FIXTURES)
    assert _rules(findings) == ["RR005", "RR006", "RR007", "RR008"]


def test_rr005_awaited_asyncio_queue_is_fine_unawaited_is_not():
    good = textwrap.dedent(
        """
        class A:
            async def f(self):
                return await self._queue.get()
        """
    )
    assert asynclint.lint_source("x.py", good) == []
    bad = good.replace("await self._queue.get()", "self._queue.get()")
    assert _rules(asynclint.lint_source("x.py", bad)) == ["RR005"]


def test_rr005_stdlib_queue_is_blocking_even_without_queue_in_the_name():
    source = textwrap.dedent(
        """
        import queue

        jobs = queue.Queue()

        async def f():
            return jobs.get()
        """
    )
    assert _rules(asynclint.lint_source("x.py", source)) == ["RR005"]


def test_rr006_lock_guarded_dual_writes_pass():
    source = textwrap.dedent(
        """
        import asyncio
        import concurrent.futures
        import threading

        class Door:
            def __init__(self):
                self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
                self._stats_lock = threading.Lock()
                self.count = 0

            def _work(self):
                with self._stats_lock:
                    self.count += 1

            async def tick(self):
                loop = asyncio.get_running_loop()
                done = await loop.run_in_executor(self._pool, self._work)
                with self._stats_lock:
                    self.count += 1
                return done
        """
    )
    assert asynclint.lint_source("x.py", source) == []


def test_rr006_confinement_manifest_declares_the_exemption(monkeypatch):
    path, source = _fixture("bad_async_rr006.py")
    assert _rules(asynclint.lint_source(path, source)) == ["RR006"]
    monkeypatch.setitem(
        asynclint.CONFINEMENT,
        "bad_async_rr006.py",
        {"Door": {"count": "test-only: single increment, torn reads ok"}},
    )
    assert asynclint.lint_source(path, source) == []


def test_rr007_stored_or_awaited_spawns_pass():
    source = textwrap.dedent(
        """
        async def main(loop, pool, work):
            t = loop.create_task(work())
            r = await loop.run_in_executor(pool, work)
            await t
            return r
        """
    )
    assert asynclint.lint_source("x.py", source) == []


def test_rr008_rejecting_handler_passes_even_via_helper():
    # the shape of the real FrontDoor._resolve/_engine: fallible work in a
    # try whose handler rejects through a one-call helper
    source = textwrap.dedent(
        """
        async def resolve(batch, collect, pool, loop):
            try:
                mean, var = await loop.run_in_executor(pool, collect, batch.handle)
                outs = demux(batch.sizes, mean, var)
            except Exception as err:
                fail_requests(batch.reqs, err)
                return
            for req, out in zip(batch.reqs, outs):
                req.future.set_result(out)


        def fail_requests(reqs, err):
            for req in reqs:
                if not req.future.done():
                    req.future.set_exception(err)
        """
    )
    assert asynclint.lint_source("x.py", source) == []
    # drop the handler and the orphaned-future path comes back
    naked = textwrap.dedent(
        """
        async def resolve(batch, collect, pool, loop):
            mean, var = await loop.run_in_executor(pool, collect, batch.handle)
            outs = demux(batch.sizes, mean, var)
            for req, out in zip(batch.reqs, outs):
                req.future.set_result(out)
        """
    )
    assert _rules(asynclint.lint_source("x.py", naked)) == ["RR008"]


def test_rr008_engine_shaped_loop_requires_crash_handling():
    source = textwrap.dedent(
        """
        async def engine(self):
            while True:
                reqs = await self._queue.get()
                batch = self._dispatch(reqs)
                pending = self._loop.create_task(self._resolve(batch))
                await pending
        """
    )
    assert _rules(asynclint.lint_source("x.py", source)) == ["RR008"]


# --------------------------------------------------------------------------
# Costs pass: pure judgment on synthetic records (no jax, no mesh)
# --------------------------------------------------------------------------


def test_cost_budget_rejects_bad_declarations():
    kw = dict(scale_axis="q_max", anchor="a", max_flop_exponent=1.3,
              max_flops=1.0, max_bytes_accessed=1.0, max_arg_bytes=1,
              max_temp_bytes=1)
    with pytest.raises(ValueError):
        inv.CostBudget(program="warp-drive", **kw)
    with pytest.raises(ValueError):  # >= quadratic allowance is vacuous
        inv.CostBudget(program="sharded-blend", **{**kw, "max_flop_exponent": 2.0})
    with pytest.raises(ValueError):
        inv.CostBudget(program="sharded-blend", **{**kw, "max_flops": 0.0})
    with pytest.raises(ValueError):
        inv.CostBudget(
            program="sharded-blend", **kw, max_device_exponent=1.5
        )
    assert set(inv.COST_BUDGETS) == {"replicated-blend", "sharded-blend"}


def test_fit_exponent():
    assert costs.fit_exponent([32, 64, 128], [10, 20, 40]) == pytest.approx(1.0)
    assert costs.fit_exponent([2, 4, 8], [4, 16, 64]) == pytest.approx(2.0)
    assert costs.fit_exponent([4, 9, 16], [7, 7, 7]) == pytest.approx(0.0)
    with pytest.raises(ValueError):
        costs.fit_exponent([2], [4])
    with pytest.raises(ValueError):
        costs.fit_exponent([2, 2], [4, 8])


def _mk_sharded(mem_exp=0.0, q_exp=1.0):
    """A synthetic sharded-blend record shaped like the real one: flat per
    device (unless ``mem_exp``), linear in q_max (unless ``q_exp``)."""
    points, axes = {}, {"devices": {}, "q_max": {}}
    for side in (2, 3, 4):
        p = side * side
        lab = f"grid={side}/q=64"
        points[lab] = {
            "flops": 220000.0, "bytes_accessed": 274000.0,
            "arg_bytes": int(7276 * (p / 16) ** mem_exp),
            "out_bytes": 528, "temp_bytes": 73728,
        }
        axes["devices"][lab] = p
    for q in (32, 64, 128):
        lab = f"grid=4/q={q}"
        points.setdefault(lab, {
            "flops": 220000.0 * (q / 64) ** q_exp,
            "bytes_accessed": 274000.0 * q / 64,
            "arg_bytes": int(7276 * q / 64),
            "out_bytes": 528 * q // 64, "temp_bytes": 73728 * q // 64,
        })
        axes["q_max"][lab] = q
    rec = {"points": points, "axes": axes}
    rec["exponents"] = costs.compute_exponents(rec)
    return rec


SHARDED_BUDGET = inv.COST_BUDGETS["sharded-blend"]


def test_cost_healthy_record_is_clean():
    assert costs.check_budget("sharded-blend/ref", _mk_sharded(), SHARDED_BUDGET) == []


def test_cost_replicated_cache_growth_caught():
    rec = _mk_sharded(mem_exp=0.5)  # per-device bytes growing with P
    findings = costs.check_budget("sharded-blend/ref", rec, SHARDED_BUDGET)
    assert _rules(findings) == ["COST-MEM-SCALING"], findings


def test_cost_qmax_flop_blowup_caught():
    rec = _mk_sharded(q_exp=2.0)  # a pairwise term crept in
    findings = costs.check_budget("sharded-blend/ref", rec, SHARDED_BUDGET)
    assert "COST-FLOP-SUPERLINEAR" in _rules(findings), findings


def test_cost_absolute_ceiling_and_missing_anchor_caught():
    import dataclasses

    rec = _mk_sharded()
    for lab in rec["points"]:
        rec["points"][lab]["temp_bytes"] = 10_000_000
    rec["exponents"] = costs.compute_exponents(rec)
    findings = costs.check_budget("sharded-blend/ref", rec, SHARDED_BUDGET)
    assert _rules(findings) == ["COST-BUDGET"], findings
    moved = dataclasses.replace(SHARDED_BUDGET, anchor="grid=9/q=9")
    findings = costs.check_budget("sharded-blend/ref", _mk_sharded(), moved)
    assert _rules(findings) == ["COST-BUDGET"]
    assert any("anchor" in f.message for f in findings)


def test_cost_baseline_drift_missing_and_improvement():
    rec = _mk_sharded()
    base = {"points": {lab: dict(m) for lab, m in rec["points"].items()}}
    assert costs.check_baseline("sharded-blend/ref", rec, base) == []
    # regression: one metric doubles -> drift finding
    worse = _mk_sharded()
    worse["points"]["grid=4/q=64"]["flops"] *= 2
    findings = costs.check_baseline("sharded-blend/ref", worse, base)
    assert _rules(findings) == ["COST-BASELINE-DRIFT"], findings
    # improvement: cheaper never gates
    better = _mk_sharded()
    better["points"]["grid=4/q=64"]["flops"] /= 2
    assert costs.check_baseline("sharded-blend/ref", better, base) == []
    # a scale point the baseline has never seen gates
    short = {"points": {k: v for k, v in base["points"].items()
                        if k != "grid=4/q=128"}}
    findings = costs.check_baseline("sharded-blend/ref", rec, short)
    assert _rules(findings) == ["COST-BASELINE-MISSING"]
    # no baseline at all gates with the how-to-fix message
    findings = costs.check_baseline("sharded-blend/ref", rec, None)
    assert _rules(findings) == ["COST-BASELINE-MISSING"]
    assert any("--update-baselines" in f.message for f in findings)


def test_lane_cost_records_cover_every_lane():
    repl_points = {
        f"n={n}": {"flops": 2300.0 * n, "bytes_accessed": 5900.0 * n,
                   "arg_bytes": 20000, "out_bytes": 8 * n + 16,
                   "temp_bytes": 576 * n}
        for n in (128, 256, 512)
    }
    repl = {"points": repl_points,
            "axes": {"n_queries": {f"n={n}": n for n in (128, 256, 512)}}}
    repl["exponents"] = costs.compute_exponents(repl)
    programs = {"replicated-blend/ref": repl, "sharded-blend/ref": _mk_sharded()}
    records = costs.lane_cost_records(programs)
    assert len(records) == len(inv.LANES)
    skipped = [r for r in records if "skipped" in r]
    measured = [r for r in records if "anchor_cost" in r]
    assert len(skipped) + len(measured) == len(records)
    # every pallas/fused lane is skipped WITH a reason; every ref lane maps
    # to its program's anchor cost and exponents
    assert skipped and all(
        r["program"].endswith(("/pallas", "/fused")) for r in skipped
    )
    for r in measured:
        assert r["anchor_cost"] is not None and r["exponents"]


# --------------------------------------------------------------------------
# Contracts pass
# --------------------------------------------------------------------------


def test_parse_and_unify():
    assert contracts.parse_shape("(S, Q, 4)") == ("S", "Q", 4)
    assert contracts.parse_shape("(N,)") == ("N",)
    env = {}
    assert contracts.unify("(S, Q)", (9, 64), env) is None
    assert env == {"S": 9, "Q": 64}
    assert contracts.unify("(S, 4)", (9, 4), env) is None
    assert contracts.unify("(S, Q)", (8, 64), env)  # S rebind -> error
    assert contracts.unify("(S, Q)", (9,), env)  # rank -> error
    assert contracts.unify("(S, 4)", (9, 5), env)  # literal -> error
    with pytest.raises(ValueError):
        contracts.parse_shape("S, Q")


def test_missing_contract_is_a_finding():
    import importlib

    target = contracts.EXPECTED_TARGETS[1]  # scatter_results, host-only
    importlib.import_module(target.rsplit(".", 1)[0])  # populate registry
    saved = contracts._REGISTRY.pop(target)
    try:
        findings, _ = contracts.run(targets=(target,), include_mesh=False)
        assert _rules(findings) == ["CONTRACT-MISSING"]
    finally:
        contracts._REGISTRY[target] = saved


def test_unknown_invariant_name_is_a_finding():
    decl = contracts.ContractDecl(
        target="repro.core.routing.scatter_results",
        spec={"returns": "(N,)", "invariants": ("made-up-claim",)},
    )
    findings = contracts.harness_scatter_results(decl)
    assert any(f.rule == "CONTRACT-DECL" for f in findings)


def test_stale_shape_declaration_fails():
    decl = contracts.ContractDecl(
        target="repro.core.routing.scatter_results",
        spec={"args": {"values": "(P, Q, 3)"}, "returns": "(N,)"},
    )
    findings = contracts.harness_scatter_results(decl)
    assert any(f.rule == "CONTRACT-SHAPE" for f in findings)


def test_host_side_contracts_clean_in_process():
    findings, report = contracts.run(include_mesh=False)
    assert findings == [], [str(f) for f in findings]
    assert "repro.core.routing.scatter_results" in report["targets_checked"]
    assert "repro.core.posterior.predict_cached_slots" in report["targets_checked"]


# --------------------------------------------------------------------------
# The CLI front door (subprocess: forces its own virtual devices)
# --------------------------------------------------------------------------


def _run_cli(*argv, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)  # the CLI must set this itself
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout,
    )


def test_cli_full_run_clean_on_shipped_codebase(tmp_path):
    out = tmp_path / "ANALYSIS.json"
    r = _run_cli("--grid", "3", "--out", str(out))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(out.read_text())
    assert report["total_findings"] == 0
    lanes = report["passes"]["hlo"]["lanes"]
    assert len(lanes) == len(inv.LANES)
    by_name = {l["lane"]: l for l in lanes}
    # the headline claims, as recorded artifacts: replicated collective-free,
    # sharded exactly the 4 composed reverse-halo ppermutes
    assert by_name["replicated/serial/single/ref"]["collectives"][
        "collective-permute"] == 0
    for name, rec in by_name.items():
        if name.startswith("sharded/"):
            assert rec["collectives"]["collective-permute"] == 4, name
            assert rec["collectives"]["all-gather"] == 0, name
    assert report["passes"]["contracts"]["targets_skipped"] == []
    # pass 4: costs gated against the committed baseline, headline shapes
    crec = report["passes"]["costs"]
    assert crec["baseline_checked"] is True
    exps = crec["programs"]["sharded-blend/ref"]["exponents"]
    assert exps["flops_vs_devices"] <= 0.05  # per-device work FLAT in P
    assert exps["arg_bytes_vs_devices"] <= 0.05  # the 1/P residency claim
    assert 0.9 <= exps["flops_vs_q_max"] <= 1.1  # linear blend, no pairwise
    assert len(crec["lanes"]) == len(inv.LANES)
    # pass 5: the shipped tree is race-clean under every RR005-RR008 rule
    assert report["passes"]["async"]["rules"] == {r: 0 for r in asynclint.RULES}
    assert report["seconds"] < 120


def test_cli_exits_nonzero_on_violations(tmp_path):
    out = tmp_path / "ANALYSIS.json"
    r = _run_cli(
        "--passes", "ast", "--root", "tests/fixtures/analysis",
        "--out", str(out),
    )
    assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(out.read_text())
    per_rule = report["passes"]["ast"]["findings_per_rule"]
    assert all(per_rule[r] >= 1 for r in ("RR001", "RR002", "RR003", "RR004"))


def test_cli_rejects_unknown_pass():
    r = _run_cli("--passes", "vibes")
    assert r.returncode == 2


# --------------------------------------------------------------------------
# Injected violation in a REAL lowered program (subprocess, own devices)
# --------------------------------------------------------------------------

_INJECT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.analysis import hlo
    from repro.analysis import invariants as inv
    from repro.launch import serve_sharded as ss

    grid = hlo.probe_grid(4)
    mesh = ss.mesh_for_grid(grid)

    # an all_gather smuggled into a shard_map program: the factors move
    gathered = jax.jit(jax.shard_map(
        lambda x: jax.lax.all_gather(x, mesh.axis_names[0]),
        mesh=mesh, in_specs=P(tuple(mesh.axis_names)), out_specs=P(),
        check_vma=False,
    ))
    txt = gathered.lower(
        jax.ShapeDtypeStruct((grid.num_partitions, 8), jnp.float32)
    ).as_text()
    lane = inv.LaneInvariant(
        name="probe", serve={"mode": "sharded"}, program="sharded-blend",
        backend="ref", max_collective_permute=8,
        forbidden_ops=inv.GATHERING_COLLECTIVES,
    )
    findings, counts = hlo.check_text(lane, txt)
    rules = sorted({f.rule for f in findings})
    assert counts["all-gather"] >= 1, counts
    assert rules == ["HLO-FORBIDDEN-OP"], findings

    # and the REAL serving program stays clean under the same invariant
    clean_txt = hlo.lower_program(("sharded-blend", "ref"))
    lane4 = inv.LaneInvariant(
        name="probe4", serve={"mode": "sharded"}, program="sharded-blend",
        backend="ref", max_collective_permute=8, min_collective_permute=4,
        forbidden_ops=inv.GATHERING_COLLECTIVES,
    )
    clean_findings, clean_counts = hlo.check_text(lane4, clean_txt)
    assert clean_findings == [] and clean_counts["collective-permute"] == 4
    print("OK")
    """
)


def test_injected_all_gather_caught_in_real_lowered_program():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _INJECT_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# --------------------------------------------------------------------------
# Cost pass through the CLI: drift gating + the --update-baselines flow
# --------------------------------------------------------------------------


def test_cli_cost_baseline_drift_gates(tmp_path):
    baseline = json.loads(
        open(os.path.join(REPO, costs.DEFAULT_BASELINE), encoding="utf-8").read()
    )
    # the committed baseline halved = today's (unchanged) program looks 2x
    # more expensive than its baseline -> drift findings, exit 1
    for rec in baseline["programs"].values():
        for metrics in rec["points"].values():
            metrics["flops"] = metrics["flops"] / 2
    stale = tmp_path / "stale_costs.json"
    stale.write_text(json.dumps(baseline))
    out = tmp_path / "ANALYSIS.json"
    r = _run_cli(
        "--passes", "costs", "--baselines", str(stale), "--out", str(out)
    )
    assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(out.read_text())
    rules = {f["rule"] for f in report["findings"]}
    assert rules == {"COST-BASELINE-DRIFT"}, rules


def test_cli_update_baselines_round_trip(tmp_path):
    fresh = tmp_path / "fresh_costs.json"
    out = tmp_path / "ANALYSIS.json"
    # no baseline yet: a plain run gates on COST-BASELINE-MISSING...
    r = _run_cli(
        "--passes", "costs", "--baselines", str(fresh), "--out", str(out)
    )
    assert r.returncode == 1
    report = json.loads(out.read_text())
    assert {f["rule"] for f in report["findings"]} == {"COST-BASELINE-MISSING"}
    # ...--update-baselines writes it and exits clean...
    r = _run_cli(
        "--passes", "costs", "--baselines", str(fresh), "--out", str(out),
        "--update-baselines",
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    payload = json.loads(fresh.read_text())
    assert set(payload["programs"]) == {"replicated-blend/ref", "sharded-blend/ref"}
    assert payload["_meta"]["tolerance"] == costs.DRIFT_TOLERANCE
    # ...and the next gated run against it is clean
    r = _run_cli(
        "--passes", "costs", "--baselines", str(fresh), "--out", str(out)
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(out.read_text())
    assert report["passes"]["costs"]["baseline_checked"] is True
    assert report["total_findings"] == 0


# --------------------------------------------------------------------------
# Injected cost violations in REAL compiled programs (subprocess)
# --------------------------------------------------------------------------

_COST_INJECT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.analysis import costs, hlo
    from repro.analysis import invariants as inv
    from repro.launch import serve_sharded as ss

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    # 1) a REPLICATED cache (in_specs P()) — per-device argument bytes now
    #    grow with the mesh, the exact failure COST-MEM-SCALING exists for
    def leaky(side):
        grid = hlo.probe_grid(side)
        mesh = ss.mesh_for_grid(grid)
        ax = mesh.axis_names[0]
        Pn = grid.num_partitions
        fn = jax.jit(jax.shard_map(
            lambda cache, q: (q @ cache.T).sum(-1),
            mesh=mesh, in_specs=(P(), P(ax)), out_specs=P(ax),
            check_vma=False,
        ))
        return costs.extract(
            fn.lower(f32(Pn * 8, 8), f32(Pn, 64, 8)).compile()
        )

    points, axes = {}, {"devices": {}, "q_max": {}}
    for side in (2, 3, 4):
        lab = f"grid={side}/q=64"
        points[lab] = leaky(side)
        axes["devices"][lab] = side * side
    for q in (32, 64, 128):
        axes["q_max"][f"grid=4/q={q}"] = q
        points.setdefault(f"grid=4/q={q}", points["grid=4/q=64"])
    rec = {"points": points, "axes": axes}
    rec["exponents"] = costs.compute_exponents(rec)
    assert rec["exponents"]["arg_bytes_vs_devices"] > 0.3, rec["exponents"]
    budget = inv.COST_BUDGETS["sharded-blend"]
    rules = sorted({f.rule for f in costs.check_budget("leaky", rec, budget)})
    assert "COST-MEM-SCALING" in rules, rules

    # 2) a PAIRWISE q x q term — flops quadratic in the block size, the
    #    exact failure COST-FLOP-SUPERLINEAR exists for
    def pairwise(q_max):
        grid = hlo.probe_grid(4)
        mesh = ss.mesh_for_grid(grid)
        ax = mesh.axis_names[0]
        Pn = grid.num_partitions
        fn = jax.jit(jax.shard_map(
            lambda q: ((q[:, :, None, :] - q[:, None, :, :]) ** 2
                       ).sum((-1, -2, -3)),
            mesh=mesh, in_specs=P(ax), out_specs=P(ax), check_vma=False,
        ))
        return costs.extract(fn.lower(f32(Pn, q_max, 2)).compile())

    points, axes = {}, {"devices": {}, "q_max": {}}
    for side in (2, 3, 4):
        lab = f"grid={side}/q=64"
        points[lab] = pairwise(64)
        axes["devices"][lab] = side * side
    for q in (32, 64, 128):
        lab = f"grid=4/q={q}"
        points.setdefault(lab, pairwise(q))
        axes["q_max"][lab] = q
    rec = {"points": points, "axes": axes}
    rec["exponents"] = costs.compute_exponents(rec)
    assert rec["exponents"]["flops_vs_q_max"] > 1.8, rec["exponents"]
    rules = sorted({f.rule for f in costs.check_budget("pairwise", rec, budget)})
    assert "COST-FLOP-SUPERLINEAR" in rules, rules

    # and the REAL programs stay inside every budget under the same judge
    programs = costs.measure_programs()
    for name, real in programs.items():
        real["exponents"] = costs.compute_exponents(real)
        clean = costs.check_budget(
            name, real, inv.COST_BUDGETS[name.split("/")[0]]
        )
        assert clean == [], [str(f) for f in clean]
    print("OK")
    """
)


def test_injected_cost_violations_caught_in_real_compiled_programs():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _COST_INJECT_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
