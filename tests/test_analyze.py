"""repro.analyze — the static contract verifier, proven with planted
violations.

The gate's whole value is that it *fires*: each test here plants one
specific contract violation (a psum in a slot fn, a host callback, rbg
on a recompute path, an np.unique in an emitter-role module) and
asserts the matching pass reports exactly that violation — and that
the inline ``# repro: allow(...)`` suppression silences exactly the
AST one.  The dialect-duality test pins the historical bug this
subsystem replaced: the seed's regex knew only the hyphenated HLO
spelling, so a planted collective in StableHLO text passed unseen.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analyze import hloscan, lint
from repro.analyze.__main__ import main as analyze_main
from repro.api import GNM, RGG, verify_contracts
from repro.distrib import engine, runtime


@pytest.fixture(scope="module")
def chunk_plan():
    return GNM(n=64, m=128, seed=1, chunks=4).plan(4)


class _PlantedCollective:
    """ChunkPlan facade whose slot fn hides a psum over the mesh axis."""

    def __init__(self, inner, tag="planted"):
        self.inner = inner
        self.tag = tag

    @property
    def num_pes(self):
        return self.inner.num_pes

    def input_arrays(self):
        return self.inner.input_arrays()

    def stream_index(self):
        return self.inner.stream_index()

    def signature(self):
        return (self.tag,) + self.inner.signature()

    def slot_fn(self):
        one = self.inner.slot_fn()

        def bad(*rows):
            payload, ok = one(*rows)
            return payload + jax.lax.psum(payload, "pe"), ok

        return bad


# --------------------------------------------------------------------------
# Pass 1: the IR scanner
# --------------------------------------------------------------------------

class TestPass1:
    def test_clean_chunk_program(self, chunk_plan):
        rep = hloscan.scan_lowered(runtime.lower_run(chunk_plan))
        assert rep.ok and not rep.collectives

    def test_clean_wave_step(self, chunk_plan):
        low = runtime.lower_wave(chunk_plan, batch=2)
        rep = hloscan.scan_lowered(low)
        assert rep.ok

    @pytest.mark.parametrize("batch", [2, 1], ids=["batched", "unbatched"])
    def test_both_wave_forms_communication_free(self, chunk_plan, monkeypatch,
                                                batch):
        """The batched wave step and the unbatched one (batch axis
        dropped in the program) both pass the zero-collective scanner,
        through ``lower_wave`` and through ``check=True`` on the form
        ``stream_waves`` dispatches."""
        low = runtime.lower_wave(chunk_plan, batch=batch)
        hloscan.assert_communication_free(low)
        cap = chunk_plan.capacity
        B = runtime.wave_schedule(chunk_plan, 1, batch).batch
        want = (cap, 2) if batch == 1 else (B, cap, 2)
        assert low.out_info[0].shape == want
        runtime.cache_clear()
        checked = []
        real = runtime.assert_communication_free

        def spy(lowered):
            checked.append(lowered.as_text())
            return real(lowered)

        monkeypatch.setattr(runtime, "assert_communication_free", spy)
        waves = list(runtime.stream_waves(chunk_plan, batch=batch,
                                          check=True))
        assert checked == [low.as_text()]
        assert waves[0].payload.shape == want

    def test_planted_psum_is_exactly_one_collective_finding(self, chunk_plan):
        low = runtime.lower_run(_PlantedCollective(chunk_plan))
        rep = hloscan.scan_lowered(low)
        assert [f.rule for f in rep.findings] == [hloscan.RULE_COLLECTIVE]
        assert "all_reduce" in set(rep.collectives)

    def test_planted_psum_fires_runtime_check(self, chunk_plan):
        """The runtime's check=True path is the same scanner: a planted
        collective aborts run() before anything executes, with the
        historical error text."""
        with pytest.raises(AssertionError,
                           match="generator lowering contains collectives"):
            runtime.run(_PlantedCollective(chunk_plan, "planted-run"),
                        check=True)

    def test_planted_psum_fires_wave_check(self, chunk_plan):
        with pytest.raises(AssertionError,
                           match="generator lowering contains collectives"):
            list(runtime.stream_waves(
                _PlantedCollective(chunk_plan, "planted-wave"), check=True))

    def test_both_ir_spellings_detected(self):
        """StableHLO (underscore) and HLO (hyphen) both match — the
        seed's hyphen-only regex let StableHLO collectives through."""
        assert hloscan.collective_ops_in(
            "  %1 = stablehlo.all_reduce %0 ...") == ["all_reduce"]
        assert hloscan.collective_ops_in(
            "  %ar = f32[8] all-reduce(%d), replica_groups={}") == ["all-reduce"]
        assert engine.collective_ops_in(
            "stablehlo.collective_permute") == ["collective_permute"]

    def test_host_callback_detected(self):
        f = jax.jit(lambda x: jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float64), x))
        rep = hloscan.scan_lowered(f.lower(jnp.ones(4)))
        assert [f.rule for f in rep.findings] == [hloscan.RULE_HOST_CALLBACK]

    def test_rbg_flagged_on_recompute_path_only(self):
        g = jax.jit(lambda: jax.random.bits(
            jax.random.key(0, impl="rbg"), (8,), dtype=jnp.uint32))
        low = g.lower()
        rep = hloscan.scan_lowered(low, hloscan.RECOMPUTE_CONTRACT)
        assert [f.rule for f in rep.findings] == [hloscan.RULE_NONDET_RNG]
        assert "DEFAULT" in rep.rng_algorithms
        # the ChunkPlan perf path may opt in: no violation there
        assert hloscan.scan_lowered(low, hloscan.GENERATOR_CONTRACT).ok

    def test_f64_and_dynamic_shape_rules(self):
        f64_text = "%0 = stablehlo.add %a, %b : tensor<4x3xf64>"
        assert hloscan.scan_text(
            f64_text, hloscan.FLOAT32_KERNEL_CONTRACT).findings
        assert hloscan.scan_text(f64_text, hloscan.GENERATOR_CONTRACT).ok
        dyn = "%1 = stablehlo.dynamic_reshape %x : tensor<?xf32>"
        rep = hloscan.scan_text(dyn)
        assert [f.rule for f in rep.findings] == [hloscan.RULE_DYNAMIC_SHAPE]

    def test_verify_contracts_front_door(self):
        reports = verify_contracts(RGG(n=32, radius=0.3, seed=2, chunks=4), 4)
        assert {r.plan_kind for r in reports} == {"pair", "point"}
        assert {r.mode for r in reports} == {"run", "wave"}
        assert all(r.ok for r in reports)


# --------------------------------------------------------------------------
# Pass 2: the AST linter
# --------------------------------------------------------------------------

EMITTER = "src/repro/core/planted.py"         # role: emitter
KERNEL = "src/repro/kernels/planted.py"       # role: kernels
SUPPORT = "src/repro/launch/planted.py"       # role: support
TESTROLE = "tests/test_planted.py"            # role: tests (exempt)


class TestPass2:
    def test_planted_np_unique_exactly_one_finding(self):
        src = "import numpy as np\nedges = np.unique(e, axis=0)\n"
        found = lint.lint_source(src, EMITTER)
        assert [f.rule for f in found] == [lint.RULE_NP_UNIQUE]
        assert found[0].line == 2

    def test_allow_comment_suppresses(self):
        src = ("import numpy as np\n"
               "edges = np.unique(e, axis=0)"
               "  # repro: allow(no-numpy-unique) oracle\n")
        assert lint.lint_source(src, EMITTER) == []

    def test_allow_comment_is_rule_specific(self):
        src = ("import numpy as np\n"
               "edges = np.unique(e, axis=0)  # repro: allow(no-raw-prngkey)\n")
        assert [f.rule for f in lint.lint_source(src, EMITTER)] == [
            lint.RULE_NP_UNIQUE]

    def test_np_unique_scoped_to_emitter_and_kernel_roles(self):
        src = "import numpy as np\nx = np.unique(y)\n"
        assert lint.lint_source(src, SUPPORT) == []
        assert lint.lint_source(src, TESTROLE) == []
        assert lint.lint_source(src, KERNEL)

    def test_python_random_flagged_everywhere_outside_tests(self):
        src = "import random\nx = random.random()\n"
        assert {f.rule for f in lint.lint_source(src, SUPPORT)} == {
            lint.RULE_PY_RANDOM}
        assert lint.lint_source(src, TESTROLE) == []

    def test_wallclock_state_flagged(self):
        src = ("import time\nimport numpy as np\n"
               "seed = time.time_ns()\nrng = np.random.default_rng()\n")
        assert [f.rule for f in lint.lint_source(src, EMITTER)] == [
            lint.RULE_WALLCLOCK, lint.RULE_WALLCLOCK]
        # a *seeded* generator is deterministic: allowed
        ok = "import numpy as np\nrng = np.random.default_rng(42)\n"
        assert lint.lint_source(ok, EMITTER) == []

    def test_collective_in_kernels_flagged(self):
        src = "import jax\ny = jax.lax.psum(x, 'pe')\n"
        assert [f.rule for f in lint.lint_source(src, KERNEL)] == [
            lint.RULE_KERNEL_COLLECTIVE]
        src2 = "from jax import lax\ny = lax.all_gather(x, 'pe')\n"
        assert [f.rule for f in lint.lint_source(src2, KERNEL)] == [
            lint.RULE_KERNEL_COLLECTIVE]
        src3 = "from repro.distrib import engine\n"
        assert [f.rule for f in lint.lint_source(src3, KERNEL)] == [
            lint.RULE_KERNEL_COLLECTIVE]

    def test_raw_prngkey_flagged_outside_prng_module(self):
        src = "import jax\nk = jax.random.PRNGKey(0)\n"
        assert [f.rule for f in lint.lint_source(src, EMITTER)] == [
            lint.RULE_RAW_PRNGKEY]
        assert lint.lint_source(src, "src/repro/core/prng.py") == []

    def test_deprecated_shim_flagged_but_not_its_definition(self):
        use = "from repro.core.er import gnm_directed\ne = gnm_directed(0, 8, 4)\n"
        rules = [f.rule for f in lint.lint_source(use, SUPPORT)]
        assert rules == [lint.RULE_DEPRECATED, lint.RULE_DEPRECATED]
        define = ("def gnm_directed(seed, n, m, P=1):\n"
                  "    return gnm_directed_impl(seed, n, m, P)\n")
        assert lint.lint_source(define, EMITTER) == []

    def test_noncounter_pair_rng_flagged_statically(self):
        flagged = [
            "from repro.api import RGG, generate\n"
            "g = generate(RGG(n=64, radius=0.1), 4, rng_impl='rbg')\n",
            "spec = RHG(n=64, avg_deg=4, gamma=2.7)\n"
            "plan = spec.plan(4, rng_impl='rbg')\n",
            "plan = make_pair_plan(rows, rng_impl='rbg')\n",
            "spec = RDG(n=64)\n"
            "for c in iter_edge_chunks(spec, 8, rng_impl='rbg'):\n"
            "    pass\n",
        ]
        for src in flagged:
            assert [f.rule for f in lint.lint_source(src, SUPPORT)] == [
                lint.RULE_NONCOUNTER_PAIR], src
        # counter impls and non-pair families stay legal
        for src in [
            "g = generate(RGG(n=64, radius=0.1), 4, rng_impl='threefry2x32')\n",
            "g = generate(GNM(n=64, m=32), 4, rng_impl='rbg')\n",
        ]:
            assert lint.lint_source(src, SUPPORT) == [], src

    def test_per_chunk_host_loop_flagged(self):
        # one finding per per-chunk call, anchored to the call line
        src = ("from repro.core.prng import host_rng\n"
               "def plan(seed, P):\n"
               "    for pe in range(P):\n"
               "        c = host_rng(seed, 1, pe)\n")
        found = lint.lint_source(src, EMITTER)
        assert [f.rule for f in found] == [lint.RULE_PER_CHUNK_LOOP]
        assert found[0].line == 4
        # comprehensions count as loops
        comp = ("from repro.distrib.engine import ChunkSpec\n"
                "specs = [ChunkSpec(k, kd, u, c, p) for k in ks]\n")
        assert [f.rule for f in lint.lint_source(comp, EMITTER)] == [
            lint.RULE_PER_CHUNK_LOOP]

    def test_per_chunk_host_loop_scope_and_exemptions(self):
        src = ("from repro.core.prng import host_rng\n"
               "for pe in range(P):\n"
               "    c = host_rng(seed, 1, pe)\n")
        # emitter-role only: support/tests stay silent
        assert lint.lint_source(src, SUPPORT) == []
        assert lint.lint_source(src, TESTROLE) == []
        # a For's iterable runs once, not per iteration
        once = ("from repro.core.prng import host_rng\n"
                "for v in host_rng(seed, 1, 0).permutation(8):\n"
                "    use(v)\n")
        assert lint.lint_source(once, EMITTER) == []
        # replayed variate draws are the sanctioned loop shape
        replay = ("from repro.core.variates import binomial\n"
                  "for k, h in enumerate(hashes):\n"
                  "    out[k] = binomial(rep.at(h), int(U[k]), float(p[k]))\n")
        assert lint.lint_source(replay, EMITTER) == []
        # line suppression works, as on the retained oracles
        allowed = ("from repro.core.prng import host_rng\n"
                   "for pe in range(P):\n"
                   "    c = host_rng(seed, 1, pe)"
                   "  # repro: allow(no-per-chunk-host-loop) oracle\n")
        assert lint.lint_source(allowed, EMITTER) == []

    def test_per_chunk_qhull_flagged(self):
        # a per-chunk host triangulation in a loop is the retired RDG
        # pattern the batched device DT replaced
        src = ("from scipy.spatial import Delaunay\n"
               "def plan(chunks):\n"
               "    for pts in chunks:\n"
               "        tri = Delaunay(pts)\n")
        found = lint.lint_source(src, EMITTER)
        assert [f.rule for f in found] == [lint.RULE_PER_CHUNK_LOOP]
        assert found[0].line == 4
        # ... and so is a per-chunk certificate batch
        cert = ("from repro.core.rdg import circumspheres\n"
                "while pending:\n"
                "    c, r = circumspheres(pts[sel])\n")
        assert [f.rule for f in lint.lint_source(cert, EMITTER)] == [
            lint.RULE_PER_CHUNK_LOOP]
        # retained oracles suppress in place (as rdg._certified_triangulation
        # and the once-per-halo-round certification batch do)
        ok = ("from scipy.spatial import Delaunay\n"
              "for pts in chunks:\n"
              "    tri = Delaunay(pts)"
              "  # repro: allow(no-per-chunk-host-loop) oracle\n")
        assert lint.lint_source(ok, EMITTER) == []
        # a single whole-batch call outside any loop is the sanctioned shape
        assert lint.lint_source(
            "from scipy.spatial import Delaunay\ntri = Delaunay(pts)\n",
            EMITTER) == []

    def test_repo_is_clean(self):
        """The shipping tree passes its own gate (inline allows and all)."""
        found = lint.lint_paths(["src/repro", "examples", "benchmarks"])
        assert found == [], "\n".join(f.format() for f in found)


# --------------------------------------------------------------------------
# the CI gate itself
# --------------------------------------------------------------------------

class TestGate:
    def test_cli_fails_on_planted_lint_violation(self, tmp_path, capsys):
        planted = tmp_path / "src" / "repro" / "core"
        planted.mkdir(parents=True)
        (planted / "bad.py").write_text(
            "import numpy as np\ne = np.unique(e, axis=0)\n")
        report = tmp_path / "report.json"
        rc = analyze_main(["--lint", str(planted), "--json", str(report),
                           "--fail-on-violation"])
        assert rc == 1
        data = json.loads(report.read_text())
        assert data["summary"]["violations"] == 1
        assert data["lint"][0]["rule"] == lint.RULE_NP_UNIQUE
        assert not data["summary"]["ok"]

    def test_cli_passes_on_clean_tree_and_writes_report(self, tmp_path):
        clean = tmp_path / "src" / "repro" / "core"
        clean.mkdir(parents=True)
        (clean / "good.py").write_text("x = 1\n")
        report = tmp_path / "report.json"
        rc = analyze_main(["--lint", str(clean), "--json", str(report),
                           "--fail-on-violation"])
        assert rc == 0
        assert json.loads(report.read_text())["summary"]["ok"]

    def test_cli_pass1_single_family(self):
        rc = analyze_main(["--families", "gnm", "--no-cost", "--lint"])
        assert rc == 0
