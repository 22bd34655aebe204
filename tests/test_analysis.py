"""The analyzers analyzed: each rule trips on its fixture, stays silent
on the negative control, and the real ``src/repro`` tree lints clean.
The jit rules are exercised on tiny synthetic programs via
``audit_traced`` (no flagship trace needed), plus one real-target smoke.
"""

import json
from functools import partial
from pathlib import Path

import pytest

from repro import streams
from repro.analysis import jit_audit, rng_lint, run_all, thread_lint
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.report import load_baseline

FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"
SRC_REPRO = Path(__file__).resolve().parent.parent / "src" / "repro"


def _codes(findings):
    return sorted(f.code for f in findings)


# -- rng_lint -----------------------------------------------------------------

def test_rng_fixture_trips_every_rule():
    src = (FIXTURES / "rng_bad.py").read_text()
    fs = rng_lint.lint_source(src, "analysis_fixtures/rng_bad.py")
    assert _codes(fs) == ["RNG001", "RNG001", "RNG002", "RNG004"]
    details = {f.code: f.detail for f in fs}
    assert details["RNG002"] == "key(1, 2, 3)"


def test_rng_negative_control_is_clean():
    src = (FIXTURES / "rng_clean.py").read_text()
    assert rng_lint.lint_source(src, "analysis_fixtures/rng_clean.py") == []


def test_rng_streams_file_is_exempt():
    src = (FIXTURES / "rng_bad.py").read_text()
    assert rng_lint.lint_source(src, "repro/streams.py") == []


def test_rng003_on_synthetic_registry_collision(monkeypatch, tmp_path):
    # a new length-2 pattern (Sym, 9967) collides with fleet_reserve_means
    bad = streams.StreamSpec(
        "bad_collider", "tuple", (streams.Sym("s"), 9967), "test-only")
    monkeypatch.setitem(streams.REGISTRY, "bad_collider", bad)
    fs = rng_lint.run(tmp_path)        # empty dir: registry check only
    assert _codes(fs) == ["RNG003"]
    assert "fleet_reserve_means" in fs[0].detail


# -- thread_lint --------------------------------------------------------------

def test_thr_fixture_trips_every_rule():
    src = (FIXTURES / "thr_bad.py").read_text()
    fs = thread_lint.lint_source(src, "analysis_fixtures/thr_bad.py")
    assert _codes(fs) == ["THR001", "THR002", "THR003", "THR003", "THR004"]
    details = {f.detail for f in fs}
    assert "Racy.unannotated" in details          # THR001
    assert "Racy.bad_none:none" in details        # THR003 (no reason)
    assert "Racy.bad_lock:badlock" in details     # THR003 (not a lock)
    assert any(d.startswith("Racy.locked:poke:") for d in details)    # THR002
    assert any(d.startswith("Racy.main_only:_worker:") for d in details)


def test_thr_negative_control_is_clean():
    src = (FIXTURES / "thr_clean.py").read_text()
    assert thread_lint.lint_source(src, "analysis_fixtures/thr_clean.py") == []


def test_thread_lint_run_scans_rt_dir(tmp_path):
    rt = tmp_path / "rt"
    rt.mkdir()
    (rt / "racy.py").write_text((FIXTURES / "thr_bad.py").read_text())
    assert "THR001" in _codes(thread_lint.run(tmp_path))
    assert thread_lint.run(tmp_path / "nowhere") == []


# -- the real tree lints clean ------------------------------------------------

def test_src_repro_rng_lints_clean():
    assert rng_lint.run(SRC_REPRO) == []


def test_src_repro_thread_lints_clean():
    assert thread_lint.run(SRC_REPRO) == []


# -- jit_audit on synthetic programs -------------------------------------------

def _trace(fn, *args):
    traced = fn.trace(*args)
    return traced, traced.lower()


def test_jit001_dropped_donation():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, donate_argnums=0)
    def f(x):
        return x.sum()      # no output matches x's shape: donation drops

    traced, lowered = _trace(f, jnp.zeros((4, 4)))
    fs = jit_audit.audit_traced("f", traced, lowered, donated_leaves=1)
    assert _codes(fs) == ["JIT001"]


def test_donation_that_aliases_is_clean():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, donate_argnums=0)
    def g(x):
        return x + 1.0

    traced, lowered = _trace(g, jnp.zeros((4, 4)))
    assert jit_audit.donation_aliases(lowered) == 1
    assert jit_audit.audit_traced("g", traced, lowered,
                                  donated_leaves=1) == []


def test_jit002_host_callback():
    import jax
    import jax.numpy as jnp
    import numpy as np

    def h(x):
        out = jax.ShapeDtypeStruct(x.shape, x.dtype)
        return jax.pure_callback(np.sin, out, x)

    traced, lowered = _trace(jax.jit(h), jnp.zeros(3, jnp.float32))
    fs = jit_audit.audit_traced("h", traced, lowered, donated_leaves=0)
    assert _codes(fs) == ["JIT002"]


def test_jit003_f64_cast():
    import jax
    import jax.numpy as jnp

    def c(x):
        return x.astype(jnp.float64).sum()

    with jax.enable_x64(True):
        traced = jax.jit(c).trace(jnp.zeros(3, jnp.float32))
        lowered = traced.lower()
    fs = jit_audit.audit_traced("c", traced, lowered, donated_leaves=0)
    assert _codes(fs) == ["JIT003"]
    # ... and the documented allowance silences it
    assert jit_audit.audit_traced("c", traced, lowered, donated_leaves=0,
                                  f64_allowance=1) == []


def test_jit004_weak_typed_carry():
    import jax
    import jax.numpy as jnp

    def l(x):  # noqa: E741
        # python-float carry: weak f32 in the lowered scan state
        return jax.lax.fori_loop(0, 3, lambda i, c: c + x.sum(), 0.0)

    traced, lowered = _trace(jax.jit(l), jnp.zeros(3, jnp.float32))
    fs = jit_audit.audit_traced("l", traced, lowered, donated_leaves=0)
    assert fs and set(_codes(fs)) == {"JIT004"}


def test_strong_carry_is_clean():
    import jax
    import jax.numpy as jnp

    def s(x):
        return jax.lax.scan(lambda c, i: (c + x.sum(), None),
                            jnp.float32(0.0),
                            jnp.arange(3, dtype=jnp.int32))[0]

    traced, lowered = _trace(jax.jit(s), jnp.zeros(3, jnp.float32))
    assert jit_audit.audit_traced("s", traced, lowered,
                                  donated_leaves=0) == []


def test_compile_counter_guards_recompiles():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    with jit_audit.CompileCounter(f, budget=1) as cc:
        f(jnp.zeros(3))
        f(jnp.zeros(3))      # cache hit
    assert cc.new_entries <= 1
    with pytest.raises(AssertionError, match="new jit cache entries"):
        with jit_audit.CompileCounter(f, budget=0):
            f(jnp.zeros(5))  # new shape: must trip the guard


def test_real_target_round_fused_audits_clean():
    # one flagship target end to end (tiny shapes, trace only)
    assert jit_audit.run(targets=("round_fused",)) == []


# -- CLI + baseline workflow ----------------------------------------------------

def test_cli_clean_on_src(tmp_path):
    out = tmp_path / "ANALYSIS.json"
    rc = analysis_main(["--check", "--no-jit", "--out", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["n_new"] == 0 and rep["n_stale_baseline"] == 0


def test_cli_fails_on_new_findings(tmp_path):
    rc = analysis_main(["--check", "--no-jit", "--root", str(FIXTURES),
                        "--baseline", str(tmp_path / "missing.json")])
    assert rc == 1


def test_cli_baseline_suppresses_and_flags_stale(tmp_path):
    findings = run_all(FIXTURES, jit=False)
    assert findings, "fixtures must produce findings"
    entries = [{"key": f.key, "why": "fixture: intentional violation"}
               for f in findings]
    entries.append({"key": "THR999:gone.py:x", "why": "no longer exists"})
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(entries))
    out = tmp_path / "ANALYSIS.json"
    rc = analysis_main(["--check", "--no-jit", "--root", str(FIXTURES),
                        "--baseline", str(bl), "--out", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["n_new"] == 0
    assert rep["stale_baseline"] == ["THR999:gone.py:x"]


def test_baseline_entries_require_why(tmp_path):
    p = tmp_path / "b.json"
    p.write_text('[{"key": "RNG001:x.py:L1"}]')
    with pytest.raises(AssertionError, match="why"):
        load_baseline(p)


def test_committed_baseline_is_empty():
    # the acceptance contract: --check passes on src/ with an EMPTY
    # baseline — nothing in the tree needs a justification today
    committed = SRC_REPRO / "analysis" / "baseline.json"
    assert json.loads(committed.read_text()) == []
