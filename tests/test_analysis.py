"""attention_tpu.analysis: the static-analysis framework.

Every pass gets fixture snippets compiled from strings — one that
triggers each rule and one that legally does not — plus suppression
and baseline round-trips, renderer schema smokes, wrapper-contract
checks for the absorbed scripts/check_* lints, and the tier-1 gate:
the committed tree is clean modulo analysis/baseline.json.
"""

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from attention_tpu.analysis import core, report
from attention_tpu.analysis.conventions import non_source_findings

pytestmark = pytest.mark.analysis

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_pass(src: str, pass_name: str,
             path: str = "attention_tpu/fake.py"):
    """Run one registered file pass on a source snippet, suppression
    applied — codes only, in source order."""
    src = textwrap.dedent(src)
    tree = ast.parse(src)
    findings = list(core.PASSES[pass_name].fn(path, tree, src))
    lines = src.splitlines()
    kept = [f for f in findings if not core.is_suppressed(f, lines)]
    return sorted(kept, key=lambda f: (f.line, f.col, f.code))


def codes(findings):
    return [f.code for f in findings]


def run_pass_indexed(src: str, pass_name: str,
                     path: str = "attention_tpu/fake.py"):
    """Like ``run_pass`` but with a single-file project index threaded
    through — exercises the interprocedural retrofits."""
    from attention_tpu.analysis.callgraph import ProjectIndex

    src = textwrap.dedent(src)
    idx = ProjectIndex.from_sources({path: src})
    tree = idx.modules[path].tree
    findings = list(core.PASSES[pass_name].fn(path, tree, src, index=idx))
    lines = src.splitlines()
    kept = [f for f in findings if not core.is_suppressed(f, lines)]
    return sorted(kept, key=lambda f: (f.line, f.col, f.code))


def run_determinism(sources: dict):
    """Run the determinism project pass over in-memory sources."""
    from attention_tpu.analysis.callgraph import ProjectIndex

    idx = ProjectIndex.from_sources(
        {p: textwrap.dedent(s) for p, s in sources.items()})
    fs = list(core.PASSES["determinism"].fn("<in-memory>", index=idx))
    return sorted(fs, key=lambda f: (f.path, f.line, f.col, f.code))


# ---------------------- purity (ATP1xx) ----------------------

def test_purity_flags_impure_calls_under_jit():
    fs = run_pass(
        """
        import time, numpy as np
        import jax

        @jax.jit
        def step(x):
            t = time.time()
            noise = np.random.normal(size=3)
            print("step", t)
            return x + noise
        """,
        "purity")
    assert codes(fs) == ["ATP101", "ATP101", "ATP101"]
    assert "time.time()" in fs[0].message


def test_purity_ignores_impure_calls_outside_traced_scopes():
    fs = run_pass(
        """
        import time, numpy as np

        def host_setup(x):
            print("building", time.time())
            return np.random.normal(size=3) + x
        """,
        "purity")
    assert fs == []


def test_purity_traces_partial_jit_and_pallas_kernels():
    fs = run_pass(
        """
        import functools, time, jax
        from jax.experimental import pallas as pl

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            time.sleep(0.1)
            return x

        def _kernel(x_ref, o_ref):
            import numpy as np
            o_ref[...] = x_ref[...] * np.random.rand()

        def launch(x):
            return pl.pallas_call(functools.partial(_kernel))(x)
        """,
        "purity")
    assert codes(fs) == ["ATP101", "ATP101"]


def test_purity_host_coercions_and_mutation():
    fs = run_pass(
        """
        import jax

        STATE = {}

        @jax.jit
        def step(x, lr):
            global STATE
            STATE["x"] = x
            scale = float(lr)
            return (x * scale).sum().item()
        """,
        "purity")
    assert codes(fs) == ["ATP103", "ATP103", "ATP102", "ATP102"]


def test_purity_captured_ref_store_in_nested_fn_is_clean():
    # the @pl.when idiom: a nested fn mutates the ENCLOSING kernel's
    # scratch refs — bound up the lexical chain, so pure by design
    fs = run_pass(
        """
        import functools
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref, acc_scr):
            @pl.when(True)
            def _tile():
                acc_scr[...] = acc_scr[...] + x_ref[...]
            o_ref[...] = acc_scr[...]

        def launch(x):
            return pl.pallas_call(_kernel)(x)
        """,
        "purity")
    assert fs == []


# ---------------------- pallas (ATP2xx) ----------------------

def test_pallas_index_map_arity_vs_grid():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4, 4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
            )(x)
        """,
        "pallas")
    assert "ATP201" in codes(fs)


def test_pallas_matching_contract_is_clean():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
            )(x)
        """,
        "pallas")
    assert fs == []


def test_pallas_block_rank_vs_index_map_return():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((1, 8, 128), lambda i, j: (i, j))],
            )(x)
        """,
        "pallas")
    assert "ATP202" in codes(fs)


def test_pallas_out_shape_dtype_vs_store():
    fs = run_pass(
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...].astype(jnp.bfloat16)

        def f(x):
            return pl.pallas_call(
                _kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            )(x)
        """,
        "pallas")
    assert codes(fs) == ["ATP203"]


def test_pallas_tile_alignment():
    """A block shape breaking BOTH tiling rules reports once — the
    strictest (lane, %128) finding, not one per rule (regression: this
    used to double-report on one line)."""
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((7, 100), lambda i: (0, i))],
            )(x)
        """,
        "pallas")
    assert codes(fs) == ["ATP204"]  # deduped: 100 % 128 wins over 7 % 8
    assert "last dim" in fs[0].message and "128" in fs[0].message


def test_pallas_tile_sublane_still_fires_alone():
    """Dedupe only collapses the double hit: a lane-clean spec with a
    bad second-minor dim still reports the sublane finding, and the
    rendered report is byte-stable across runs."""
    src = """
        from jax.experimental import pallas as pl

        def f(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((7, 128), lambda i: (0, i))],
            )(x)
        """
    fs = run_pass(src, "pallas")
    assert codes(fs) == ["ATP204"]
    assert "second-minor" in fs[0].message
    assert report.render_text(fs) == report.render_text(
        run_pass(src, "pallas"))


def test_pallas_variable_shapes_are_skipped():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern, block_q, d, grid):
            return pl.pallas_call(
                kern,
                grid=grid,
                in_specs=[pl.BlockSpec((1, block_q, d),
                                       lambda i, j, k: (0, i, 0))],
            )(x)
        """,
        "pallas")
    assert fs == []


# ---------------------- precision (ATP3xx) ----------------------

def test_precision_lowprec_dot_without_preferred_type():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f(q, k):
            return jnp.dot(q.astype(jnp.bfloat16), k)
        """,
        "precision")
    assert codes(fs) == ["ATP301"]


def test_precision_preferred_type_is_clean():
    fs = run_pass(
        """
        import jax
        import jax.numpy as jnp

        def f(q, k):
            qb = q.astype(jnp.bfloat16)
            s = jax.lax.dot_general(
                qb, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jnp.einsum("mn,nd->md", s, k,
                              preferred_element_type=jnp.float32)
        """,
        "precision")
    assert fs == []


def test_precision_tracks_names_and_upcasts():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f(q, k):
            q8 = q.astype(jnp.int8)
            k32 = k.astype(jnp.float32)
            a = jnp.einsum("md,nd->mn", q8, k32)   # q8 still int8: flag
            b = jnp.matmul(k32, k32)               # fp32: clean
            return a, b
        """,
        "precision")
    assert codes(fs) == ["ATP301"]


def test_precision_matmul_operator_and_exp():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f(q, k, s):
            y = q.astype(jnp.bfloat16) @ k
            p = jnp.exp(s.astype(jnp.bfloat16))
            ok = jnp.exp(s)
            return y, p, ok
        """,
        "precision")
    assert codes(fs) == ["ATP301", "ATP302"]


# ---------------------- errors (ATP4xx) ----------------------

def test_errors_flags_generic_raises_in_typed_paths():
    src = """
        from attention_tpu.ops.paged import OutOfPagesError

        def admit(n):
            if n < 0:
                raise ValueError("n must be >= 0")
            if n > 100:
                raise RuntimeError("pool wedged")
            raise OutOfPagesError("typed: fine")
        """
    fs = run_pass(src, "errors", path="attention_tpu/engine/x.py")
    assert codes(fs) == ["ATP402", "ATP401"]
    # the same file outside engine//chaos/ is out of the rule's scope
    assert run_pass(src, "errors", path="attention_tpu/ops/x.py") == []


# ---------------------- durability (ATP701) ----------------------

def test_durability_flags_truncating_open_without_replace():
    src = """
        import os

        def save_torn(path, blob):
            with open(path, "wb") as f:
                f.write(blob)

        def save_atomic(path, blob):
            import tempfile
            fd, tmp = tempfile.mkstemp(dir=".")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)

        def append_wal(path, line):
            with open(path, "ab") as f:
                f.write(line)

        def read(path):
            with open(path, "rb") as f:
                return f.read()
        """
    fs = run_pass(src, "durability",
                  path="attention_tpu/engine/snapshot.py")
    assert codes(fs) == ["ATP701"]
    assert fs[0].line == 5
    # only the three durable-persistence modules are in scope
    assert run_pass(src, "durability",
                    path="attention_tpu/engine/engine.py") == []


def test_durability_inline_suppression_and_module_level():
    src = """
        import os

        with open("state.json", "w") as f:  # atp: disable=ATP701
            f.write("{}")

        with open("torn.json", "w") as f:
            f.write("{}")
        """
    fs = run_pass(src, "durability",
                  path="attention_tpu/tuning/cache.py")
    assert codes(fs) == ["ATP701"]
    assert fs[0].line == 7


# ---------------------- conventions (ATP5xx/ATP601) ----------------------

def test_obs_naming_pass_literal_vs_dynamic():
    fs = run_pass(
        """
        from attention_tpu import obs

        C = obs.counter("EngineSteps")
        S = obs.span("just_one_segment")
        G = obs.gauge(dynamic_name)
        OK = obs.counter("engine.steps.run")
        """,
        "obs-naming")
    assert codes(fs) == ["ATP501", "ATP501"]


def test_obs_trace_event_pass_literal_vs_dynamic():
    """ATP504: literal trace event names outside the closed enum are
    flagged; legal events and dynamic names are not — and the digest
    instrument joined the ATP501 name check."""
    fs = run_pass(
        """
        from attention_tpu import obs
        from attention_tpu.obs import trace

        def f(rid, dyn):
            trace.record(rid, "teleported", tick=1)
            trace.record(rid, "finished", tick=2)
            trace.record(rid, dyn, tick=3)
            trace.record(rid)
            obs.digest("BadDigestName")
            obs.digest("engine.digest.ttft_steps")
        """,
        "obs-naming")
    assert codes(fs) == ["ATP504", "ATP501"]
    assert "teleported" in fs[0].message
    assert "TRACE_EVENTS" in fs[0].message


def test_obs_trace_event_suppression():
    fs = run_pass(
        """
        from attention_tpu.obs import trace

        def f(rid):
            trace.record(rid, "not_an_event", tick=0)  # atp: disable=ATP504
        """,
        "obs-naming")
    assert fs == []


def test_non_source_guard():
    fs = non_source_findings([
        "attention_tpu/ops/flash.py",
        "attention_tpu/ops/flash.pyc",
        "tests/__pycache__/test_x.py",
        "attention_tpu/_native/libattn.so",
        "tests/test_ops.py",
    ])
    assert sorted(f.path for f in fs) == [
        "attention_tpu/_native/libattn.so",
        "attention_tpu/ops/flash.pyc",
        "tests/__pycache__/test_x.py",
    ]
    assert {f.code for f in fs} == {"ATP601"}


# ---------------------- frozen-series pin (ATP505) ----------------------

def _frozen_index(extra: dict):
    """A project index holding the REAL naming module plus synthetic
    creator/consumer sources."""
    from attention_tpu.analysis.callgraph import ProjectIndex

    with open(os.path.join(_REPO, "attention_tpu/obs/naming.py")) as f:
        sources = {"attention_tpu/obs/naming.py": f.read()}
    sources.update({p: textwrap.dedent(s) for p, s in extra.items()})
    return ProjectIndex.from_sources(sources)


def _all_creators_source():
    """Source that creates every frozen series via its constant —
    mirrors how the real creation sites are written."""
    import attention_tpu.obs.naming as naming

    consts = {v: k for k, v in vars(naming).items()
              if k.startswith("SERIES_")}
    lines = ["from attention_tpu.obs import naming",
             "def wire(obs):"]
    for name, kind in naming.FROZEN_SERIES.items():
        lines.append(f"    obs.{kind}(naming.{consts[name]}, 'd')")
    return "\n".join(lines) + "\n"


def test_frozen_series_pin_clean_when_all_created():
    from attention_tpu.analysis.conventions import frozen_series_findings

    idx = _frozen_index({"attention_tpu/fake/wiring.py":
                         _all_creators_source()})
    assert frozen_series_findings(idx) == []


def test_frozen_series_pin_fires_on_drift():
    """All three ATP505 drift classes: a frozen name nobody creates, a
    creation under the wrong instrument kind, and a consumer re-typing
    a frozen name as a literal."""
    from attention_tpu.analysis.conventions import frozen_series_findings

    idx = _frozen_index({
        "attention_tpu/fake/wiring.py": """
            from attention_tpu.obs.naming import SERIES_SLO_BUDGET
            def wire(obs):
                obs.counter(SERIES_SLO_BUDGET, 'd')  # gauge, not counter
            """,
        "attention_tpu/obs/slo.py":
            'x = "frontend.slo.burn_rate"\n',
    })
    fs = frozen_series_findings(idx)
    assert all(f.code == "ATP505" for f in fs)
    msgs = [f.message for f in fs]
    assert any("never created" in m for m in msgs)
    assert any("created here via counter()" in m for m in msgs)
    assert any("re-typed as a" in m for m in msgs)
    # the literal finding lands on the consumer module
    lit = next(f for f in fs if "re-typed" in f.message)
    assert lit.path == "attention_tpu/obs/slo.py"


def test_frozen_series_pin_ignores_docstring_mentions():
    from attention_tpu.analysis.conventions import frozen_series_findings

    consumer_src = (
        '"""Mirrors land under frontend.capacity.headroom."""\n'
        "def f():\n"
        '    "and obs.capacity.cost_per_token too"\n'
    )
    idx = _frozen_index({
        "attention_tpu/fake/wiring.py": _all_creators_source(),
        "attention_tpu/obs/capacity.py": consumer_src,
    })
    assert frozen_series_findings(idx) == []


def test_frozen_series_pin_runs_in_tree_gate():
    """The pass is registered, index-aware, and project-scoped, so
    `cli analyze` / check_all run it automatically."""
    p = core.PASSES["frozen-series"]
    assert p.scope == "project" and p.needs_index
    assert p.codes == ("ATP505",)


# ---------------------- bench trend (ATP506) ----------------------

def _write_bench(root, rnd, kernel_ms):
    with open(os.path.join(root, f"BENCH_r{rnd:02d}.json"), "w") as f:
        json.dump({"n": rnd, "parsed": {
            "value": 1000.0, "detail": {
                "tpu_kernel_ms": kernel_ms,
                "mxu_utilization_of_peak": 0.9}}}, f)


def test_bench_trend_committed_trajectory_is_clean():
    """The gate must pass on the repo's own committed history — it
    keys on kernel ms, not the speedup value (whose serial baseline
    legitimately re-based between rounds)."""
    from attention_tpu.analysis import benchtrend

    assert benchtrend.trend_problems(_REPO) == []
    rows = benchtrend.trend_rows(_REPO)
    assert len(rows) >= 5
    assert all("error" not in r for r in rows)


def test_bench_trend_fires_on_regression(tmp_path):
    from attention_tpu.analysis import benchtrend

    root = str(tmp_path)
    _write_bench(root, 1, 3.0)
    _write_bench(root, 2, 3.2)   # +6.7%: inside budget
    _write_bench(root, 3, 3.6)   # +12.5%: regression
    problems = benchtrend.trend_problems(root)
    assert len(problems) == 1
    assert "BENCH_r03.json" in problems[0]
    assert "+12.5%" in problems[0]
    fs = list(core.PASSES["bench-trend"].fn(root))
    assert [f.code for f in fs] == ["ATP506"]


def test_bench_trend_flags_unparsable_round(tmp_path):
    from attention_tpu.analysis import benchtrend

    root = str(tmp_path)
    _write_bench(root, 1, 3.0)
    with open(os.path.join(root, "BENCH_r02.json"), "w") as f:
        f.write('{"parsed": {}}')
    problems = benchtrend.trend_problems(root)
    assert len(problems) == 1 and "unparsable" in problems[0]


def test_bench_trend_refuses_round_without_provenance(tmp_path):
    """From r11 on, a round must record max_mode + mesh_shards in
    parsed.detail; earlier rounds are grandfathered (r01/r02 predate
    max_mode entirely)."""
    from attention_tpu.analysis import benchtrend

    root = str(tmp_path)
    _write_bench(root, 10, 3.0)  # pre-cutoff: no provenance demanded
    _write_bench(root, 11, 3.0)
    problems = benchtrend.trend_problems(root)
    assert len(problems) == 1
    assert "BENCH_r11.json" in problems[0]
    assert "max_mode" in problems[0] and "mesh_shards" in problems[0]
    # complete provenance: clean
    with open(os.path.join(root, "BENCH_r11.json"), "w") as f:
        json.dump({"parsed": {"value": 1.0, "detail": {
            "tpu_kernel_ms": 3.0, "max_mode": "flash-d",
            "mesh_shards": [1, 4]}}}, f)
    assert benchtrend.trend_problems(root) == []
    # one field missing still refuses
    with open(os.path.join(root, "BENCH_r12.json"), "w") as f:
        json.dump({"parsed": {"value": 1.0, "detail": {
            "tpu_kernel_ms": 3.0, "max_mode": "flash-d"}}}, f)
    problems = benchtrend.trend_problems(root)
    assert len(problems) == 1 and "mesh_shards" in problems[0]


# ---------------------- determinism (ATP8xx) ----------------------

def test_atp801_wall_clock_into_artifact_sink():
    fs = run_determinism({
        "attention_tpu/engine/snap.py": """
            import json
            import time

            def save(path, state):
                state["saved_at"] = time.time()
                return json.dumps(state)
            """,
    })
    assert codes(fs) == ["ATP801"]
    assert "time.time" in fs[0].message


def test_atp801_interprocedural_summary_chain():
    """The metrics shape: summary() stamps a wall, a sibling method
    feeds it into record_run — the taint crosses two call edges."""
    fs = run_determinism({
        "attention_tpu/engine/m.py": """
            import time

            class Metrics:
                def summary(self):
                    return {"wall_s": time.perf_counter()}

                def emit(self, tr):
                    tr.record_run(self.summary())
            """,
    })
    assert codes(fs) == ["ATP801"]
    assert fs[0].path == "attention_tpu/engine/m.py"


def test_atp801_scheduling_decision_on_wall_clock():
    """The fixture chaos token-parity invariants catch dynamically —
    a wall-clock deadline steering admission — caught statically."""
    fs = run_determinism({
        "attention_tpu/engine/sched.py": """
            import time

            def admit(queue, deadline_s):
                if time.monotonic() > deadline_s:
                    return None
                return queue[0]
            """,
    })
    assert codes(fs) == ["ATP801"]
    assert "decision" in fs[0].message


def test_atp801_sanctioned_idioms_are_clean():
    fs = run_determinism({
        "attention_tpu/engine/ok.py": """
            import time

            def step(hist, rec, tick):
                t0 = time.perf_counter()
                work = tick * 2
                hist.observe(time.perf_counter() - t0)  # save_ms idiom
                rec.record_step(tick, work)             # virtual clock
                return work
            """,
    })
    assert fs == []


def test_atp802_unseeded_randomness_and_seeded_chain():
    fs = run_determinism({
        "attention_tpu/chaos/fz.py": """
            import random

            import numpy as np

            def flip():
                return random.random() < 0.5

            def seeded(seed):
                rng = np.random.default_rng(seed)
                return rng.random()
            """,
    })
    assert codes(fs) == ["ATP802"]
    assert "random.random" in fs[0].message


def test_atp802_helper_returning_randomness():
    """The helper lives outside the RNG dirs, so only the call site in
    frontend/ fires — via the callee's return-taint summary."""
    fs = run_determinism({
        "attention_tpu/idgen.py": """
            import uuid

            def fresh_id():
                return uuid.uuid4().hex
            """,
        "attention_tpu/frontend/sub.py": """
            from attention_tpu.idgen import fresh_id

            def submit(req):
                req["id"] = fresh_id()
                return req
            """,
    })
    assert codes(fs) == ["ATP802"]
    assert fs[0].path == "attention_tpu/frontend/sub.py"
    assert "uuid.uuid4" in fs[0].message


def test_atp802_prngkey_threaded_vs_loose():
    fs = run_determinism({
        "attention_tpu/engine/keys.py": """
            import jax

            def mk_loose(t):
                return jax.random.PRNGKey(t)

            def mk_threaded(cfg):
                return jax.random.PRNGKey(cfg.seed)

            def mk_literal():
                return jax.random.PRNGKey(0)
            """,
    })
    assert codes(fs) == ["ATP802"]
    assert fs[0].line == 5          # mk_loose's PRNGKey(t)


def test_atp803_unordered_into_order_sensitive_consumers():
    fs = run_determinism({
        "attention_tpu/obs/agg.py": """
            def series(names, extra):
                s = set(names)
                return list(s)

            def series_ok(names):
                return sorted(set(names))

            def pick_first(ids):
                for rid in frozenset(ids):
                    return rid
            """,
    })
    assert codes(fs) == ["ATP803", "ATP803"]
    assert fs[0].line == 4          # list(s)
    assert fs[1].line == 10         # early-exit loop
    assert "sorted" in fs[0].message


def test_atp803_inline_suppression_is_honoured():
    fs = run_determinism({
        "attention_tpu/obs/agg.py": """
            def series(names):
                s = set(names)
                return list(s)  # atp: disable=ATP803
            """,
    })
    assert fs == []


def test_atp804_float_accumulation_over_unordered():
    fs = run_determinism({
        "attention_tpu/obs/stat.py": """
            def total(xs):
                acc = 0.0
                for x in set(xs):
                    acc += x
                return acc

            def total2(xs):
                return sum(set(xs))

            def count(xs):
                return len(set(xs))

            def biggest(xs):
                return max(set(xs))
            """,
    })
    assert codes(fs) == ["ATP804", "ATP804"]
    for f in fs:
        assert f.severity is core.Severity.WARNING


# ---------------------- interprocedural retrofits ----------------------

def test_purity_one_level_helper_from_jit_body():
    src = """
        import time
        import jax

        def _log(x):
            print("x", x, time.time())

        def _pure(x):
            return x * 2

        @jax.jit
        def step(x):
            _log(x)
            return _pure(x)
        """
    fs = run_pass_indexed(src, "purity")
    assert codes(fs) == ["ATP101"]
    assert "_log" in fs[0].message and "trace time" in fs[0].message
    # without the index the helper blind spot is (by design) invisible
    assert run_pass(src, "purity") == []


def test_precision_one_level_helper_dots_lowprec_arg():
    src = """
        import jax
        import jax.numpy as jnp

        def _proj(a, b):
            return jnp.dot(a, b)

        def _proj_ok(a, b):
            a = a.astype(jnp.float32)
            return jnp.dot(a, b)

        @jax.jit
        def f(q, k):
            qb = q.astype(jnp.bfloat16)
            return _proj(qb, k) + _proj_ok(qb, k)
        """
    fs = run_pass_indexed(src, "precision")
    assert codes(fs) == ["ATP301"]
    assert "_proj" in fs[0].message
    assert run_pass(src, "precision") == []


def test_errors_scope_covers_obs_tree():
    src = """
        def check(q):
            if q < 0:
                raise ValueError("q must be >= 0")
        """
    assert codes(run_pass(src, "errors",
                          path="attention_tpu/obs/x.py")) == ["ATP402"]


# ---------------------- suppression ----------------------

def test_inline_suppression_by_code_and_bare():
    base = """
        import time, jax

        @jax.jit
        def step(x):
            t = time.time(){}
            return x + t
        """
    assert codes(run_pass(base.format(""), "purity")) == ["ATP101"]
    assert run_pass(base.format("  # atp: disable=ATP101"),
                    "purity") == []
    assert run_pass(base.format("  # atp: disable"), "purity") == []
    # a different code on the directive does NOT suppress
    assert codes(run_pass(base.format("  # atp: disable=ATP301"),
                          "purity")) == ["ATP101"]


# ---------------------- baseline ----------------------

def _finding(code="ATP402", path="attention_tpu/engine/x.py",
             msg="raise ValueError in a typed-error path"):
    return core.Finding(code, msg, path, 10, 4)


def test_baseline_roundtrip_and_matching(tmp_path):
    entries = [
        report.BaselineEntry(code="ATP402",
                             path="attention_tpu/engine/x.py",
                             justification="API-boundary validation",
                             count=2),
    ]
    p = tmp_path / "baseline.json"
    report.save_baseline(str(p), entries)
    loaded = report.load_baseline(str(p))
    assert loaded == entries

    remaining, problems = report.apply_baseline(
        [_finding(), _finding()], loaded)
    assert remaining == [] and problems == []

    # count drift (a third ValueError appears) fails the gate
    remaining, problems = report.apply_baseline(
        [_finding(), _finding(), _finding()], loaded)
    assert remaining == [] and any("count drift" in p for p in problems)

    # stale entries (finding fixed but entry kept) fail the gate too
    remaining, problems = report.apply_baseline([], loaded)
    assert any("stale" in p for p in problems)


def test_baseline_rejects_silent_entries(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({
        "version": 1,
        "entries": [{"code": "ATP402",
                     "path": "attention_tpu/engine/x.py",
                     "justification": "   "}],
    }))
    with pytest.raises(ValueError, match="no justification"):
        report.load_baseline(str(p))


# ---------------------- renderers ----------------------

def test_json_and_sarif_schema_smoke():
    fs = [_finding(), _finding(code="ATP101", msg="impure host call")]
    j = json.loads(report.render_json(fs, ["stale baseline entry: x"]))
    assert j["version"] == 1
    assert j["counts"] == {"ATP101": 1, "ATP402": 1}
    assert len(j["findings"]) == 2 and len(j["baseline_problems"]) == 1
    assert j["findings"][0]["severity"] in ("error", "warning")

    s = json.loads(report.render_sarif(fs))
    assert s["version"] == "2.1.0"
    run = s["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rule_ids == {"ATP101", "ATP402"}
    res = run["results"][0]
    assert res["ruleId"] in rule_ids
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] and loc["region"]["startLine"]


def test_text_render_clean_and_dirty():
    assert report.render_text([]) == "analysis OK\n"
    text = report.render_text([_finding()])
    assert "ATP402" in text and "1 finding(s)" in text


def test_github_render_round_trips_the_finding():
    """The workflow-command line carries back every field of the
    finding — file, line, 1-based col, code title, message — and a
    clean run emits nothing (no noise annotations in CI)."""
    f = _finding()
    line = report.render_github([f]).rstrip("\n")
    kind, rest = line[2:].split(" ", 1)
    props_s, message = rest.split("::", 1)
    props = dict(kv.split("=", 1) for kv in props_s.split(","))
    assert kind == ("error" if f.severity is core.Severity.ERROR
                    else "warning")
    assert props["file"] == f.path
    assert int(props["line"]) == f.line
    assert int(props["col"]) == f.col + 1
    assert props["title"] == f.code
    assert message == f.message
    # data escaping: %, newlines, and property commas can't break the
    # command syntax
    weird = core.Finding("ATP402", "50% worse,\nreally", "a,b.py", 3, 0)
    line = report.render_github([weird]).rstrip("\n")
    assert "\n" not in line
    assert "file=a%2Cb.py" in line
    assert line.endswith("::50%25 worse,%0Areally")
    # whole-file findings (line == 0) carry only file=
    wf = core.Finding("ATP402", "m", "x.py")
    assert "line=" not in report.render_github([wf])
    # clean tree: empty output, and baseline problems still annotate
    assert report.render_github([]) == ""
    assert report.render_github([], ["stale entry"]).startswith(
        "::error file=attention_tpu/analysis/baseline.json")


# ---------------------- registry ----------------------

def test_every_registered_pass_has_codes_and_stable_ids():
    assert set(core.PASSES) == {"purity", "pallas", "precision",
                                "errors", "obs-naming", "shipped-table",
                                "tolerance-ledger", "source-only-tree",
                                "durability", "determinism",
                                "frozen-series", "bench-trend",
                                "shapes", "sharding"}
    for p in core.PASSES.values():
        assert p.codes, p.name
        assert p.scope in ("file", "project")
    # the interprocedural passes declare it, plain ones stay index-free
    assert core.PASSES["determinism"].needs_index
    assert core.PASSES["purity"].needs_index
    assert core.PASSES["precision"].needs_index
    assert core.PASSES["shapes"].needs_index
    assert core.PASSES["sharding"].needs_index
    assert core.PASSES["pallas"].needs_index  # ATP902 symbolic upgrade
    assert not core.PASSES["errors"].needs_index
    # the symbolic upgrade lives in the pallas pass, not a new one
    assert "ATP902" in core.PASSES["pallas"].codes
    # stable public ids: retiring/renumbering any of these is a break
    assert {"ATP001", "ATP101", "ATP102", "ATP103", "ATP201", "ATP202",
            "ATP203", "ATP204", "ATP301", "ATP302", "ATP401", "ATP402",
            "ATP501", "ATP502", "ATP503", "ATP504", "ATP505",
            "ATP506", "ATP601",
            "ATP701", "ATP801", "ATP802", "ATP803", "ATP804",
            "ATP901", "ATP902", "ATP903", "ATP904", "ATP905", "ATP906"
            } <= set(core.CODES)


# ---------------------- CLI + wrappers + the tier-1 gate ----------------

def _run(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=_REPO,
                          capture_output=True, text=True, env=env, **kw)


def test_legacy_wrappers_keep_contract():
    """The absorbed check_* scripts: same happy-path stdout, exit 0."""
    r = _run(["scripts/check_obs_names.py"])
    assert r.returncode == 0 and r.stdout == "obs names OK\n"
    r = _run(["scripts/check_shipped_table.py"])
    assert r.returncode == 0
    assert r.stdout.startswith("OK   ")
    assert r.stdout.endswith("entries, schema valid\n")
    r = _run(["scripts/check_tolerances.py"])
    assert r.returncode == 0
    assert r.stdout.startswith("OK   ")
    assert r.stdout.endswith("budgets match chaos/budgets.py\n")


def test_tree_wide_analysis_is_clean_modulo_baseline():
    """THE gate this PR lands: the committed tree has zero unbaselined
    findings (scripts/check_all.py is what CI runs)."""
    r = _run(["scripts/check_all.py"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout == "analysis OK\n"


def _tree_wide_timings():
    """``check_all.py --timings`` on the tree: its stderr and the one
    ``total`` line's milliseconds."""
    r = _run(["scripts/check_all.py", "--timings"])
    assert r.returncode == 0, r.stdout + r.stderr
    total_lines = [ln for ln in r.stderr.splitlines()
                   if ln.strip().endswith("ms  total")]
    assert len(total_lines) == 1, r.stderr
    return r.stderr, float(total_lines[0].strip().split()[0])


def test_tree_wide_run_itemizes_its_timings():
    """The whole tree — index build plus every pass — analyzes clean
    and says where the time went: one total, and a line each for the
    interprocedural index, the determinism pass and the two symbolic
    interpreters."""
    stderr, _ = _tree_wide_timings()
    assert "<index>" in stderr and "determinism" in stderr
    assert "shapes" in stderr and "sharding" in stderr


@pytest.mark.slow
def test_tree_wide_run_fits_the_time_budget():
    """ISSUE 13's perf contract: the whole tree analyzes in <= 5 s.
    A wall-clock limit means something only on an idle machine, so it
    stays out of tier-1, which runs beside five busy workers."""
    _, total_ms = _tree_wide_timings()
    assert total_ms <= 5000.0, f"tree-wide analysis took {total_ms} ms"


def test_cli_analyze_changed_exits_clean():
    """--changed (with the call-graph reverse closure folded in) on the
    current tree: whatever is dirty must be clean modulo baseline."""
    r = _run(["-m", "attention_tpu.cli", "analyze", "--changed"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_analyze_changed_analysis_edit_escalates(monkeypatch):
    """Regression: editing a file under analysis/ changes what every
    pass would say about every file, so --changed must escalate to a
    tree-wide run (rel_paths=None) — the call-graph closure can't model
    an analyzer edit.  A non-analyzer edit keeps the partial run."""
    import attention_tpu.cli as cli
    from attention_tpu import analysis
    from attention_tpu.analysis import core as acore

    captured = {}

    def spy(root, rel_paths=None, timings=None, index=None):
        captured["rel_paths"] = rel_paths
        return []

    class _IdxStub:
        def files_calling(self, paths):
            return set()

    monkeypatch.setattr(analysis, "analyze", spy)
    monkeypatch.setattr(acore, "build_index", lambda root: _IdxStub())
    monkeypatch.setattr(
        cli, "_changed_files",
        lambda root, base: ["attention_tpu/analysis/shapes.py"])
    assert cli.main(["analyze", "--changed", "--no-baseline"]) == 0
    assert captured["rel_paths"] is None  # escalated: full tree

    monkeypatch.setattr(
        cli, "_changed_files",
        lambda root, base: ["attention_tpu/ops/flash.py"])
    assert cli.main(["analyze", "--changed", "--no-baseline"]) == 0
    assert captured["rel_paths"] == ["attention_tpu/ops/flash.py"]


def test_cli_analyze_json_on_fixture_file(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(
        """
        import time, jax

        @jax.jit
        def step(x):
            return x + time.time()
        """))
    from attention_tpu.cli import main

    rc = _run(["-m", "attention_tpu.cli", "analyze", str(bad),
               "--format", "json"])
    assert rc.returncode == 1
    payload = json.loads(rc.stdout)
    assert payload["counts"].get("ATP101") == 1
    assert main(["analyze", "--list-codes"]) == 0


def test_check_all_github_shorthand_annotates(tmp_path):
    """scripts/check_all.py --github == cli analyze --format github:
    findings come back as ::error workflow-command lines CI can pin to
    the diff."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(
        """
        import time, jax

        @jax.jit
        def step(x):
            return x + time.time()
        """))
    r = _run(["scripts/check_all.py", str(bad), "--github"])
    assert r.returncode == 1, r.stdout + r.stderr
    hits = [ln for ln in r.stdout.splitlines() if "title=ATP101" in ln]
    assert hits, r.stdout
    assert hits[0].startswith("::error file=")
    assert ",line=" in hits[0] and ",col=" in hits[0]
