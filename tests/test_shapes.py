"""Symbolic shape & sharding abstract interpretation (ATP901-906).

String fixtures per code, both directions: a provable violation fires,
an unprovable one stays silent (the never-guess contract), and
``# atp: disable`` is honored.  Plus the tree gate: the real
``parallel/serving.py`` shard_map sites are *discovered* and certified
clean — silence backed by found sites, not by a pass that never ran.
"""

import ast
import os
import textwrap

import pytest

from attention_tpu.analysis import core, report, shapes, sharding

pytestmark = pytest.mark.analysis

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_pass(src: str, pass_name: str,
             path: str = "attention_tpu/fake.py"):
    src = textwrap.dedent(src)
    tree = ast.parse(src)
    findings = list(core.PASSES[pass_name].fn(path, tree, src))
    lines = src.splitlines()
    kept = [f for f in findings if not core.is_suppressed(f, lines)]
    return sorted(kept, key=lambda f: (f.line, f.col, f.code))


def run_pass_indexed(src: str, pass_name: str,
                     path: str = "attention_tpu/fake.py"):
    from attention_tpu.analysis.callgraph import ProjectIndex

    src = textwrap.dedent(src)
    idx = ProjectIndex.from_sources({path: src})
    tree = idx.modules[path].tree
    findings = list(core.PASSES[pass_name].fn(path, tree, src, index=idx))
    lines = src.splitlines()
    kept = [f for f in findings if not core.is_suppressed(f, lines)]
    return sorted(kept, key=lambda f: (f.line, f.col, f.code))


def codes(findings):
    return [f.code for f in findings]


# ---------------------- the Dim lattice ----------------------

def test_dim_lattice_algebra():
    a, b = shapes.sym("n"), shapes.sym("h")
    assert shapes.con(8).concrete and not a.concrete
    assert shapes.dim_mul(a, b) == shapes.dim_mul(b, a)
    assert shapes.dim_div(shapes.dim_mul(a, b), b) == a
    assert shapes.dim_div(a, b) is None  # not structurally provable
    assert shapes.dim_div(shapes.con(12), shapes.con(5)) is None


def test_facts_certify_but_never_fire():
    f = shapes.Facts()
    n = shapes.sym("n")
    assert not f.divisible(n, shapes.con(128))  # unknown, not "no"
    f.add(n, shapes.con(256))
    assert f.divisible(n, shapes.con(256))
    assert f.divisible(n, shapes.con(128))  # 256-divisible => 128 too
    assert f.divisible(shapes.con(512), shapes.con(128))  # concrete
    assert f.divisible(shapes.dim_mul(n, shapes.con(8)), shapes.con(8))


# ---------------------- ATP901: provable shape mismatch -------------

def test_atp901_dot_contraction_mismatch_fires():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f():
            a = jnp.zeros((4, 7))
            b = jnp.zeros((9, 5))
            return jnp.dot(a, b)
        """,
        "shapes")
    assert codes(fs) == ["ATP901"]
    assert "7" in fs[0].message and "9" in fs[0].message


def test_atp901_matmul_operator_and_concat_axis_fire():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f():
            a = jnp.ones((2, 3))
            b = jnp.ones((5, 4))
            c = a @ b
            d = jnp.concatenate([jnp.zeros((2, 8)),
                                 jnp.zeros((3, 8))], axis=1)
            return c, d
        """,
        "shapes")
    assert codes(fs) == ["ATP901", "ATP901"]


def test_atp901_einsum_binds_one_letter_two_sizes():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f():
            q = jnp.zeros((4, 16))
            k = jnp.zeros((8, 32))
            return jnp.einsum("bd,nd->bn", q, k)
        """,
        "shapes")
    assert codes(fs) == ["ATP901"]


def test_atp901_through_interprocedural_summary():
    fs = run_pass_indexed(
        """
        import jax.numpy as jnp

        def helper(a):
            return a.T

        def f():
            x = jnp.zeros((4, 7))
            y = helper(x)
            z = jnp.zeros((9, 5))
            return jnp.dot(y, z)
        """,
        "shapes")
    assert codes(fs) == ["ATP901"]


def test_atp901_symbolic_operands_stay_silent():
    """Unknown shapes, a conditional re-bind, and a loop re-bind are
    all unprovable — silence, never a guess."""
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f(a, b, flag, xs):
            c = jnp.zeros((4, 7))
            if flag:
                c = jnp.zeros((4, 9))
            for x in xs:
                b = x
            return jnp.dot(a, b), jnp.dot(c, jnp.zeros((9, 5)))
        """,
        "shapes")
    assert fs == []


def test_atp901_disable_comment_honored():
    fs = run_pass(
        """
        import jax.numpy as jnp

        def f():
            a = jnp.zeros((4, 7))
            b = jnp.zeros((9, 5))
            return jnp.dot(a, b)  # atp: disable=ATP901
        """,
        "shapes")
    assert fs == []


# ---------------------- ATP902: symbolic Pallas contracts -----------

def test_atp902_variable_block_dim_resolves_bad():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            block_d = 100
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, block_d), lambda i: (0, i))],
            )(x)
        """,
        "pallas")
    assert codes(fs) == ["ATP902"]
    assert "100" in fs[0].message and "128" in fs[0].message


def test_atp902_symbolic_grid_rank_vs_index_map():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            grid = (4, 4)
            return pl.pallas_call(
                kern,
                grid=grid,
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],
            )(x)
        """,
        "pallas")
    assert codes(fs) == ["ATP902"]


def test_atp902_namedtuple_field_propagates():
    """BlockSizes().block_q reaches the spec by constant propagation
    through the NamedTuple constructor."""
    fs = run_pass(
        """
        from typing import NamedTuple
        from jax.experimental import pallas as pl

        class BlockSizes(NamedTuple):
            block_q: int = 100
            block_k: int = 128

        def f(x, kern):
            bs = BlockSizes()
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, bs.block_q),
                                       lambda i: (0, i))],
            )(x)
        """,
        "pallas")
    assert codes(fs) == ["ATP902"]


def test_atp902_unprovable_and_certified_stay_silent():
    """A parameter-bound block dim is symbolic: without a fact it is
    unprovable, with an ``assert % 128`` it is certified — silent
    either way (absence of a fact is not evidence)."""
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern, block_q):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, block_q), lambda i: (0, i))],
            )(x)

        def g(x, kern, block_q):
            assert block_q % 128 == 0
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, block_q), lambda i: (0, i))],
            )(x)
        """,
        "pallas")
    assert fs == []


def test_atp902_disable_comment_honored():
    fs = run_pass(
        """
        from jax.experimental import pallas as pl

        def f(x, kern):
            block_d = 100
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, block_d),  # atp: disable=ATP902
                                       lambda i: (0, i))],
            )(x)
        """,
        "pallas")
    assert fs == []


# ---------------------- ATP903: PartitionSpec geometry --------------

_SHARD_PRELUDE = textwrap.dedent("""
    import functools
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
""")


def shard_fixture(body: str) -> str:
    return _SHARD_PRELUDE + textwrap.dedent(body)


def test_atp903_spec_longer_than_provable_rank_fires():
    fs = run_pass(shard_fixture("""
        def head(devs):
            q = jnp.zeros((4, 8))
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, None, "kv"),),
                               out_specs=P(None, None))
            def run(x):
                return x

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP903"]
    assert "rank 2" in fs[0].message


def test_atp903_unknown_axis_name_fires():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "tp"),),
                               out_specs=P(None, None))
            def run(x):
                return x

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP903"]
    assert "'tp'" in fs[0].message


def test_atp903_variable_axis_entry_stays_silent():
    """A spec entry that is a *variable* could be None — never treated
    as provably sharded (this is exactly serving.py's idiom)."""
    fs = run_pass(shard_fixture("""
        def head(q, devs, axis_name):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, axis_name),),
                               out_specs=P(None, None))
            def run(x):
                return x

            return run(q)
        """),
        "sharding")
    assert fs == []


# ---------------------- ATP904: shard divisibility ------------------

def test_atp904_sharded_dim_without_guard_fires():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            b, d = q.shape
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P("kv", None),),
                               out_specs=P(None, None))
            def run(x):
                return x

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP904"]
    assert "MeshConfigError" in fs[0].message


def test_atp904_guard_fact_certifies():
    """The ``if b % n_dev: raise`` guard IS the divisibility fact —
    the static twin of MeshConfigError accepts it (and an unknown
    operand shape is silent too)."""
    fs = run_pass(shard_fixture("""
        def head(q, r, devs, n_dev):
            b, d = q.shape
            if b % n_dev:
                raise ValueError("uneven")
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P("kv", None), P("kv", None)),
                               out_specs=P(None, None))
            def run(x, y):
                return x

            return run(q, r)
        """),
        "sharding")
    assert fs == []


# ---------------------- ATP905: silent cross-shard partials ---------

def test_atp905_reduction_over_sharded_dim_fires():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"),),
                               out_specs=P(None))
            def run(x):
                return jnp.sum(x, axis=1)

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP905"]
    assert "silent partial" in fs[0].message


def test_atp905_einsum_contraction_fires():
    fs = run_pass(shard_fixture("""
        def head(q, w, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"), P(None, None)),
                               out_specs=P(None, None))
            def run(x, y):
                return jnp.einsum("bk,kd->bd", x, y)

            return run(q, w)
        """),
        "sharding")
    assert codes(fs) == ["ATP905"]


def test_atp905_collective_or_unresolved_call_silences():
    """A psum makes the partial correct; an unresolvable call makes
    collective-freedom unprovable — both silent."""
    fs = run_pass(shard_fixture("""
        import jax

        def head(q, devs, fixup):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"),),
                               out_specs=P(None))
            def run(x):
                p = jnp.sum(x, axis=1)
                return jax.lax.psum(p, "kv")

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"),),
                               out_specs=P(None))
            def run2(x):
                p = jnp.sum(x, axis=1)
                return fixup(p)

            return run(q), run2(q)
        """),
        "sharding")
    assert fs == []


def test_atp905_in_tree_clean_helper_still_fires():
    """The collective-freedom proof follows in-tree call edges: a body
    that routes the partial through a provably collective-free helper
    is still a silent partial."""
    fs = run_pass_indexed(shard_fixture("""
        def _scale(a):
            return jnp.exp(a)

        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"),),
                               out_specs=P(None))
            def run(x):
                p = jnp.sum(x, axis=1)
                return _scale(p)

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP905"]


def test_atp905_unsharded_axis_reduction_is_silent():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"),),
                               out_specs=P("kv"))
            def run(x):
                return jnp.sum(x, axis=0)

            return run(q)
        """),
        "sharding")
    assert fs == []


def test_atp905_disable_comment_honored():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, "kv"),),
                               out_specs=P(None))
            def run(x):
                return jnp.sum(x, axis=1)  # atp: disable=ATP905

            return run(q)
        """),
        "sharding")
    assert fs == []


# ---------------------- ATP906: out_specs vs return -----------------

def test_atp906_tuple_length_mismatch_fires():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, None),),
                               out_specs=(P(None, None), P(None, None)))
            def run(x):
                return x, x, x

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP906"]
    assert "2-tuple" in fs[0].message and "3-tuple" in fs[0].message


def test_atp906_spec_longer_than_return_rank_fires():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, None),),
                               out_specs=P(None, None, None))
            def run(x):
                y = jnp.zeros((4, 8))
                return y

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP906"]


def test_atp906_unknown_mesh_axis_fires():
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, None),),
                               out_specs=P("tp"))
            def run(x):
                return x

            return run(q)
        """),
        "sharding")
    assert codes(fs) == ["ATP906"]


def test_atp906_pytree_prefix_is_silent():
    """A single spec against a tuple return is a legal pytree prefix;
    an unknown return rank is unprovable.  Both silent."""
    fs = run_pass(shard_fixture("""
        def head(q, devs):
            mesh = Mesh(devs, ("kv",))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, None),),
                               out_specs=P(None, None))
            def run(x):
                return x, x

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(None, None),),
                               out_specs=P(None, None, None))
            def run2(x):
                return x

            return run(q), run2(q)
        """),
        "sharding")
    assert fs == []


# ---------------------- the tree gate -------------------------------

def test_serving_and_ragged_paged_are_certified_clean():
    """The static precondition for the 2D mesh refactor: serving.py's
    shard_map sites are *found* (3+, so silence is a proof over real
    sites, not a pass that never ran) and carry zero ATP9xx findings
    with zero baseline entries; ragged_paged.py has no shard_map site
    at all (its in_specs belong to a Pallas PrefetchScalarGridSpec),
    and is equally clean."""
    serving = "attention_tpu/parallel/serving.py"
    ragged = "attention_tpu/ops/ragged_paged.py"
    index = core.build_index(_REPO)

    interp = shapes.interp_for(serving, index.modules[serving].tree,
                               index)
    sites = sharding._find_sites(interp)
    assert len(sites) >= 3
    assert all(site.calls for site in sites)  # call sites discovered

    rinterp = shapes.interp_for(ragged, index.modules[ragged].tree,
                                index)
    assert sharding._find_sites(rinterp) == []

    findings = core.analyze(_REPO, rel_paths=[serving, ragged],
                            index=index)
    atp9 = [f for f in findings if f.code.startswith("ATP9")
            and f.path in (serving, ragged)]
    assert atp9 == []

    entries = report.load_baseline(report.default_baseline_path(_REPO))
    assert [e for e in entries if e.code.startswith("ATP9")] == []
