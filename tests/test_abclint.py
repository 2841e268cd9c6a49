"""abclint self-tests: one violating + one clean fixture per rule, pragma
and baseline mechanics, and the tier-1 "repo is clean against the committed
baseline" regression.

Fixture files are written into tmp repo trees shaped like the real one
(``src/repro/serve/...`` etc.) because every pass scopes by relpath."""
import json
import os
import textwrap

import pytest

from tools.abclint import engine
from tools.abclint.__main__ import main as abclint_main
from tools.abclint.passes import ALL_PASSES


def lint_fixture(tmp_path, relpath, code):
    """Write ``code`` at ``relpath`` under a tmp repo root and lint it."""
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    return engine.run_passes(
        ALL_PASSES, root=str(tmp_path), scope=(relpath,)
    )


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# pass 1 — retrace hazards
# ---------------------------------------------------------------------------


def test_abc101_jit_inside_function(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/fx.py", """
        import jax

        def per_call(step, x):
            return jax.jit(step)(x)
    """)
    assert rules_of(findings) == ["ABC101"]


def test_abc101_clean_module_level_and_lru_factory(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/fx.py", """
        import functools
        import jax

        @jax.jit
        def decorated(x):
            return x + 1

        module_level = jax.jit(decorated)

        @functools.lru_cache(maxsize=None)
        def programs(step):
            return jax.jit(step)
    """)
    assert findings == []


def test_abc102_lambda_to_jit(tmp_path):
    findings = lint_fixture(tmp_path, "benchmarks/bx.py", """
        import jax

        def run(x):
            f = jax.jit(lambda y: y + 1)
            return f(x)
    """)
    assert rules_of(findings) == ["ABC101", "ABC102"]


def test_abc103_branch_on_tracer(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/fx.py", """
        import jax.numpy as jnp

        def f(x):
            if jnp.max(x) > 0:
                return x
            return -x
    """)
    assert rules_of(findings) == ["ABC103"]


def test_abc103_clean_static_dtype_predicate(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/fx.py", """
        import jax.numpy as jnp

        def f(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x
            return -x
    """)
    assert findings == []


def test_abc104_per_token_decode_over_draft(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        def verify(backend, plan, slot):
            for j, tok in enumerate(plan.draft):
                logits, _ = backend.decode_step(tok, slot, plan.start + j)
    """)
    assert rules_of(findings) == ["ABC104"]


def test_abc104_clean_single_verify_pass_and_out_of_scope(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        def verify(backend, plan, slot, max_chunk):
            choices = backend.verify_draft(
                plan.tokens, slot, plan.start, max_chunk
            )
            for tok in plan.draft:
                record(tok)
            return choices
    """)
    assert findings == []
    findings = lint_fixture(tmp_path, "src/repro/models/mx.py", """
        def reference(api, params, cache, draft, cfg):
            for j, tok in enumerate(draft):
                logits, cache = api.decode_step(params, tok, cache, j, cfg)
            return cache
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# pass 2 — host-sync leaks (scope: serve/ + core/cascade.py)
# ---------------------------------------------------------------------------


def test_abc201_item(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        def f(x):
            return x.item()
    """)
    assert rules_of(findings) == ["ABC201"]


def test_abc202_bool_over_array_expr(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        import jax.numpy as jnp

        def f(x):
            return bool(jnp.any(x))
    """)
    assert rules_of(findings) == ["ABC202"]


def test_abc202_clean_fetched_scalar(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        from repro.core.cascade import host_fetch

        def f(x):
            return bool(host_fetch(x)[0])
    """)
    assert findings == []


def test_abc203_np_asarray(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        import numpy as np

        def f(x):
            return np.asarray(x)
    """)
    assert rules_of(findings) == ["ABC203"]


def test_abc203_clean_wrapping_explicit_fetch(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        import numpy as np
        from repro.core.cascade import host_fetch

        def f(x):
            return np.asarray(host_fetch(x), np.int32)
    """)
    assert findings == []


def test_abc204_device_get_outside_fetch(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/sx.py", """
        import jax

        def f(x):
            return jax.device_get(x)
    """)
    assert rules_of(findings) == ["ABC204"]


def test_host_sync_out_of_scope_and_transport_whitelist(tmp_path):
    # transport.py IS the metered boundary; train/ is out of scope entirely
    code = """
        import jax

        def f(x):
            return jax.device_get(x).item()
    """
    assert lint_fixture(tmp_path, "src/repro/serve/transport.py", code) == []
    assert lint_fixture(tmp_path, "src/repro/train/tx.py", code) == []


# ---------------------------------------------------------------------------
# pass 3 — determinism (scope: core/ + serve/)
# ---------------------------------------------------------------------------


def test_abc301_builtin_hash(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/dx.py", """
        def digest(b):
            return hash(b)
    """)
    assert rules_of(findings) == ["ABC301"]


def test_abc301_clean_crc32(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/dx.py", """
        import zlib

        def digest(b):
            return zlib.crc32(b)
    """)
    assert findings == []


def test_abc302_set_iteration(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/dx.py", """
        def f(xs):
            return [x + 1 for x in set(xs)]
    """)
    assert rules_of(findings) == ["ABC302"]


def test_abc302_clean_sorted_set(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/dx.py", """
        def f(xs):
            return [x + 1 for x in sorted(set(xs))]
    """)
    assert findings == []


def test_abc303_wall_clock_and_seed_free_rng(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/dx.py", """
        import time
        import numpy as np

        def f():
            a = time.time()
            b = np.random.rand(3)
            rng = np.random.default_rng()
            return a, b, rng
    """)
    assert rules_of(findings) == ["ABC303", "ABC303", "ABC303"]


def test_abc303_clean_metering_clock_and_seeded_rng(tmp_path):
    # perf_counter is the blessed metering clock for ABC303; in serve/ it
    # would additionally trip ABC601 (injectable-clock discipline), so the
    # fixture lives in core/
    findings = lint_fixture(tmp_path, "src/repro/core/dx.py", """
        import time
        import numpy as np

        def f():
            t = time.perf_counter()
            rng = np.random.default_rng(0)
            return t, rng
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# pass 4 — kernel contract (scope: kernels/)
# ---------------------------------------------------------------------------


def _kernel_pkg(tmp_path, name, files):
    pkg = tmp_path / "src" / "repro" / "kernels" / name
    pkg.mkdir(parents=True)
    for fn, code in files.items():
        (pkg / fn).write_text(textwrap.dedent(code))
    return engine.run_passes(
        ALL_PASSES, root=str(tmp_path), scope=("src/repro/kernels",)
    )


def test_abc401_missing_trio(tmp_path):
    findings = _kernel_pkg(tmp_path, "mykern", {"ops.py": "X = 1\n"})
    assert rules_of(findings) == ["ABC401"]
    assert "kernel.py" in findings[0].message
    assert "ref.py" in findings[0].message


def test_abc401_clean_full_trio(tmp_path):
    findings = _kernel_pkg(
        tmp_path, "mykern",
        {"ops.py": "X = 1\n", "kernel.py": "Y = 1\n", "ref.py": "Z = 1\n"},
    )
    assert findings == []


def test_abc403_pallas_call_without_interpret(tmp_path):
    findings = _kernel_pkg(tmp_path, "mykern", {
        "ops.py": "", "ref.py": "",
        "kernel.py": """
            import functools
            import jax
            import jax.experimental.pallas as pl

            @functools.partial(jax.jit, static_argnames=("block",))
            def launch(x, block):
                if x.shape[0] % block:
                    raise ValueError(x.shape)
                return pl.pallas_call(_body)(x)
        """,
    })
    assert rules_of(findings) == ["ABC403"]


def test_abc404_bare_assert_in_dispatcher(tmp_path):
    findings = _kernel_pkg(tmp_path, "mykern", {
        "kernel.py": "", "ref.py": "",
        "ops.py": """
            def dispatch(x, block):
                assert x.shape[0] % block == 0
                return x
        """,
    })
    assert rules_of(findings) == ["ABC404"]


def test_abc405_launch_without_divisibility_guard(tmp_path):
    findings = _kernel_pkg(tmp_path, "mykern", {
        "ops.py": "", "ref.py": "",
        "kernel.py": """
            import jax
            import jax.experimental.pallas as pl

            @jax.jit
            def launch(x):
                return pl.pallas_call(_body, interpret=True)(x)
        """,
    })
    assert rules_of(findings) == ["ABC405"]


def test_kernel_contract_clean_guarded_launch(tmp_path):
    findings = _kernel_pkg(tmp_path, "mykern", {
        "ops.py": """
            def dispatch(x, block):
                if x.shape[0] % block != 0:
                    raise ValueError(
                        f"size {x.shape[0]} not divisible by {block}"
                    )
                return x
        """,
        "ref.py": "def oracle(x):\n    return x\n",
        "kernel.py": """
            import functools
            import jax
            import jax.experimental.pallas as pl

            @functools.partial(jax.jit, static_argnames=("block", "interpret"))
            def launch(x, block, *, interpret):
                if x.shape[0] % block != 0:
                    raise ValueError((x.shape, block))
                return pl.pallas_call(_body, interpret=interpret)(x)
        """,
    })
    assert findings == []


# ---------------------------------------------------------------------------
# pass 5 — serving memory
# ---------------------------------------------------------------------------


def test_abc501_init_cache_in_serving_layer(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        from repro.models import api

        def build(cfg, n_slots, max_seq):
            return api.init_cache(cfg, n_slots, max_seq)
    """)
    assert rules_of(findings) == ["ABC501"]


def test_abc501_out_of_scope_in_models(tmp_path):
    # batch-generation caches in the model layer are not slot memory
    findings = lint_fixture(tmp_path, "src/repro/models/mx.py", """
        from repro.models import api

        def build(cfg, batch, max_seq):
            return api.init_cache(cfg, batch, max_seq)
    """)
    assert findings == []


def test_abc502_e_stacked_zeros(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        import jax
        import jax.numpy as jnp

        def stack(values0, E):
            return jax.tree.map(
                lambda v: jnp.zeros((E,) + v.shape, v.dtype), values0
            )
    """)
    assert rules_of(findings) == ["ABC502"]


def test_abc502_clean_plain_shapes(tmp_path):
    # literal-tuple and same-shape allocations are not the stack idiom
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        import jax.numpy as jnp

        def build(v, n_pages, page_size):
            a = jnp.zeros((n_pages, page_size), jnp.float32)
            b = jnp.zeros(v.shape, v.dtype)
            return a, b
    """)
    assert findings == []


def test_memory_pragma_covers_oracle_site(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        from repro.models import api

        def build(cfg, n_slots, max_seq):
            # abclint: disable=ABC501(fixture parity oracle justification)
            return api.init_cache(cfg, n_slots, max_seq)
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# pragma mechanics
# ---------------------------------------------------------------------------


def test_pragma_same_line_suppresses(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/px.py", """
        def f(b):
            return hash(b)  # abclint: disable=ABC301(fixture justification)
    """)
    assert findings == []


def test_pragma_comment_line_above_suppresses(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/px.py", """
        def f(b):
            # abclint: disable=ABC301(fixture justification)
            return hash(b)
    """)
    assert findings == []


def test_pragma_without_reason_is_abc001(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/px.py", """
        def f(b):
            return hash(b)  # abclint: disable=ABC301
    """)
    # the reasonless pragma is itself a finding AND suppresses nothing
    assert rules_of(findings) == ["ABC001", "ABC301"]


def test_unused_pragma_is_abc002(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/px.py", """
        def f(b):
            return b  # abclint: disable=ABC301(nothing here to suppress)
    """)
    assert rules_of(findings) == ["ABC002"]


def test_pragma_wrong_rule_does_not_suppress(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/px.py", """
        def f(b):
            return hash(b)  # abclint: disable=ABC302(wrong rule id)
    """)
    assert rules_of(findings) == ["ABC002", "ABC301"]


# ---------------------------------------------------------------------------
# pass 6 — telemetry discipline
# ---------------------------------------------------------------------------


def test_abc601_raw_perf_counter_in_serve(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        import time

        def step(self):
            t0 = time.perf_counter()
            return time.perf_counter() - t0
    """)
    assert rules_of(findings) == ["ABC601", "ABC601"]


def test_abc601_clean_injected_clock_and_link_physics(tmp_path):
    # holding the clock FUNCTION (assignment) and calling through it is the
    # blessed pattern; time.monotonic/time.sleep are transport link physics
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        import time

        from repro.obs import perf_clock

        class C:
            def __init__(self, obs):
                self._clock = obs.clock if obs else perf_clock

            def step(self):
                t0 = self._clock()
                time.sleep(0.0)
                now = time.monotonic()
                return self._clock() - t0, now
    """)
    assert findings == []


def test_abc601_out_of_scope_outside_serve(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/core/mx.py", """
        import time

        def bench():
            return time.perf_counter()
    """)
    assert findings == []


def test_abc602_stats_dict_mutation(tmp_path):
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        class C:
            def __init__(self):
                self.stats = {"n": 0}
                self._stats = {"m": 0}

            def step(self, stats):
                self.stats["n"] += 1
                self._stats["m"] = 5
                stats["k"] = 2
    """)
    assert rules_of(findings) == ["ABC602", "ABC602", "ABC602"]


def test_abc602_clean_registry_and_plain_dicts(tmp_path):
    # registry metrics and unrelated dicts stay silent — only stats-named
    # subscript targets are the legacy surface
    findings = lint_fixture(tmp_path, "src/repro/serve/mx.py", """
        class C:
            def __init__(self, sc):
                self._c = sc.counter("n")
                self.table = {}

            def step(self, r):
                self._c.add(1)
                self.table[r] = 1
                view = self.table["x"] if "x" in self.table else None
                return view
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def _one_finding(tmp_path):
    p = tmp_path / "src" / "repro" / "core" / "bx.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def f(b):\n    return hash(b)\n")
    return "src/repro/core/bx.py"


def test_baseline_suppresses_and_reports(tmp_path):
    rel = _one_finding(tmp_path)
    findings = engine.run_passes(ALL_PASSES, root=str(tmp_path), scope=(rel,))
    (f, fp), = engine.fingerprinted(findings)
    baseline = {fp: {"fingerprint": fp, "rule": f.rule, "reason": "audited"}}
    res = engine.run(
        ALL_PASSES, root=str(tmp_path), scope=(rel,), baseline=baseline
    )
    assert res.ok
    assert res.findings == [] and rules_of(res.baselined) == ["ABC301"]


def test_baseline_fingerprint_survives_line_moves(tmp_path):
    rel = _one_finding(tmp_path)
    findings = engine.run_passes(ALL_PASSES, root=str(tmp_path), scope=(rel,))
    (_, fp), = engine.fingerprinted(findings)
    # shift the offending line down: content fingerprint must not change
    p = tmp_path / rel
    p.write_text("X = 1\n\n\ndef f(b):\n    return hash(b)\n")
    moved = engine.run_passes(ALL_PASSES, root=str(tmp_path), scope=(rel,))
    (_, fp2), = engine.fingerprinted(moved)
    assert fp2 == fp


def test_stale_baseline_entry_fails_run(tmp_path):
    rel = _one_finding(tmp_path)
    baseline = {"deadbeefdeadbeef": {
        "fingerprint": "deadbeefdeadbeef", "rule": "ABC301",
        "reason": "the code this suppressed is gone",
    }}
    res = engine.run(
        ALL_PASSES, root=str(tmp_path), scope=(rel,), baseline=baseline
    )
    assert not res.ok and len(res.stale_baseline) == 1


def test_baseline_load_rejects_empty_reason(tmp_path):
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps({"version": 1, "entries": [
        {"fingerprint": "ab" * 8, "rule": "ABC301", "reason": "  "}
    ]}))
    with pytest.raises(engine.BaselineError, match="no justification"):
        engine.load_baseline(str(bp))


def test_write_baseline_preserves_old_reasons(tmp_path):
    rel = _one_finding(tmp_path)
    findings = engine.run_passes(ALL_PASSES, root=str(tmp_path), scope=(rel,))
    (_, fp), = engine.fingerprinted(findings)
    bp = tmp_path / "baseline.json"
    engine.write_baseline(str(bp), findings, {fp: {"reason": "kept reason"}})
    loaded = engine.load_baseline(str(bp))
    assert loaded[fp]["reason"] == "kept reason"
    # fresh entries get an empty reason, which load_baseline refuses
    engine.write_baseline(str(bp), findings, {})
    with pytest.raises(engine.BaselineError):
        engine.load_baseline(str(bp))


# ---------------------------------------------------------------------------
# CLI + the tier-1 repo-clean regression
# ---------------------------------------------------------------------------


def test_cli_list_rules_and_usage_error(capsys):
    assert abclint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("ABC001", "ABC101", "ABC201", "ABC301", "ABC401"):
        assert rule in out
    assert abclint_main(["no/such/path.py"]) == 2


def test_repo_is_abclint_clean_against_committed_baseline():
    """Tier-1 invariant: the repo lints clean — every finding is either
    fixed, pragma'd with a reason, or in the committed justified baseline,
    and no baseline entry is stale."""
    baseline = engine.load_baseline(
        os.path.join(engine.REPO, engine.BASELINE_DEFAULT)
    )
    res = engine.run(ALL_PASSES, baseline=baseline)
    msg = "\n".join(f.render() for f in res.findings)
    msg += "".join(f"\nstale: {e}" for e in res.stale_baseline)
    assert res.ok, f"abclint regressions:\n{msg}"


def test_cli_json_report(capsys):
    # full default scope: a narrower scope would strand the committed
    # baseline entries as stale (by design — the baseline only shrinks)
    assert abclint_main(["--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["findings"] == []
    assert report["stale_baseline"] == []
    assert report["summary"]["baselined"] == 1


def test_baseline_guard_shrink_only(tmp_path, capsys):
    """CI guard: fingerprints may leave the baseline, never join it."""
    from tools.abclint.baseline_guard import main as guard_main

    def write(path, fps):
        path.write_text(json.dumps(
            {"version": 1,
             "entries": [{"fingerprint": f, "rule": "ABC203",
                          "path": "x.py", "snippet": "s", "reason": "r"}
                         for f in fps]}))

    old, new = tmp_path / "old.json", tmp_path / "new.json"
    write(old, ["aaaa", "bbbb"])
    write(new, ["aaaa"])
    assert guard_main([str(old), str(new)]) == 0  # shrank: ok
    write(new, ["aaaa", "bbbb", "cccc"])
    assert guard_main([str(old), str(new)]) == 1  # grew: fail
    assert "cccc" in capsys.readouterr().err
    # missing base file (first PR that introduces a baseline) == empty set
    assert guard_main([str(tmp_path / "absent.json"), str(old)]) == 1
    assert guard_main(["a", "b", "c"]) == 2  # usage


def test_baseline_guard_default_new_is_committed_baseline(capsys):
    from tools.abclint.baseline_guard import main as guard_main

    committed = os.path.join(engine.REPO, engine.BASELINE_DEFAULT)
    assert guard_main([committed]) == 0  # committed vs itself: no growth
    assert "baseline ok" in capsys.readouterr().out
