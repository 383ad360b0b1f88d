"""repro.analysis.lint: every rule proven by a paired good/bad fixture.

The bad fixtures are the repo's actual shipped-bug taxonomy, reproduced
minimally: the PR 5 serve-engine aliased-dispatch race, the PR 3 seed-offset
stream collision, the pre-PR 6 torn checkpoint publish, the PR 3 sort-in-
fori_loop miscompile shape, plus the host-sync / static-arg / donation /
impure-scan classes the sweep engine is built to avoid. The final test lints
the real tree — the linter must exit clean on its own repository, which is
also the permanent regression guard for rule false positives.

Fixtures live in string literals, so linting THIS file sees no fixture AST.
"""
import json
import textwrap

import pytest

from repro.analysis import lint
from repro.analysis.lint import cli
from repro.analysis.lint.core import RULES, lint_paths, lint_source
from repro.analysis.lint.reporters import render_json, render_text


def _lint(src, rule=None):
    rules = [rule] if rule else None
    return lint_source(textwrap.dedent(src), "fixture.py", rules=rules)


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------- aliased-buffer-dispatch
# the historical serve/engine.py decode race: a VIEW of the mutable pending
# buffer handed to jax, then pending mutated while dispatch is in flight
ENGINE_RACE_BAD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    class Engine:
        def __init__(self):
            self.pending = np.zeros((4, 8), np.int32)
            self._step = jax.jit(lambda t: t + 1)

        def step(self, s, nxt):
            toks = jnp.asarray(self.pending[:, None])
            out = self._step(toks)
            self.pending[s] = nxt
            return out
"""

ENGINE_RACE_GOOD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    class Engine:
        def __init__(self):
            self.pending = np.zeros((4, 8), np.int32)
            self._step = jax.jit(lambda t: t + 1)

        def step(self, s, nxt):
            toks = jnp.asarray(np.array(self.pending[:, None], copy=True))
            out = self._step(toks)
            self.pending[s] = nxt
            return out
"""


def test_engine_race_fixture_is_flagged():
    found = _lint(ENGINE_RACE_BAD)
    assert "aliased-buffer-dispatch" in _rules_of(found)
    assert any("self.pending" in f.message for f in found)


def test_snapshotted_dispatch_is_clean():
    assert _lint(ENGINE_RACE_GOOD) == []


# ------------------------------------------------------- rng-offset-derivation
# the historical trace.py stream bug: seed, seed+1, seed+2 streams collide
# across adjacent sweep configs
SEED_OFFSET_BAD = """
    import numpy as np
    import jax

    def streams(seed):
        spec = np.random.default_rng(seed + 1)
        arrivals = jax.random.PRNGKey(2 * seed)
        return spec, arrivals
"""

SEED_OFFSET_GOOD = """
    import numpy as np
    import jax

    def streams(seed):
        children = np.random.SeedSequence(seed).spawn(2)
        spec = np.random.default_rng(children[0])
        arrivals = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
        tupled = np.random.default_rng((100, seed))
        return spec, arrivals, tupled
"""


def test_seed_offset_fixture_is_flagged():
    found = _lint(SEED_OFFSET_BAD)
    assert _rules_of(found) == {"rng-offset-derivation"}
    assert len(found) == 2  # both the +1 and the 2*seed derivations


def test_spawned_and_folded_streams_are_clean():
    assert _lint(SEED_OFFSET_GOOD) == []


# ---------------------------------------------------------------- torn-publish
TORN_PUBLISH_BAD = """
    import os

    def publish(tmp):
        with open(tmp, "w") as f:
            f.write("{}")
        os.replace(tmp, "manifest.json")
"""

TORN_PUBLISH_GOOD = """
    import os

    def publish(tmp, payload_tmp, payload):
        with open(payload_tmp, "wb") as f:
            f.write(b"bytes")
            f.flush()
            os.fsync(f.fileno())
        os.replace(payload_tmp, payload)
        os.replace(tmp, "manifest.json")
"""


def test_unfsynced_manifest_publish_is_flagged():
    found = _lint(TORN_PUBLISH_BAD)
    assert _rules_of(found) == {"torn-publish"}


def test_fsync_ordered_publish_is_clean():
    assert _lint(TORN_PUBLISH_GOOD) == []


# ---------------------------------------------------------------- sort-in-loop
SORT_IN_LOOP_BAD = """
    import jax
    import jax.numpy as jnp

    def plan(pref, n):
        def body(i, acc):
            order = jnp.argsort(-pref)
            return acc + order[0]
        return jax.lax.fori_loop(0, n, body, 0)
"""

SORT_IN_LOOP_GOOD = """
    import jax
    import jax.numpy as jnp

    def plan(pref, n):
        order = jnp.argsort(-pref)  # hoisted: computed once, outside

        def body(i, acc):
            return acc + order[i]
        return jax.lax.fori_loop(0, n, body, 0)
"""


def test_sort_inside_fori_loop_is_flagged():
    found = _lint(SORT_IN_LOOP_BAD)
    assert _rules_of(found) == {"sort-in-loop"}


def test_hoisted_sort_is_clean():
    assert _lint(SORT_IN_LOOP_GOOD) == []


# -------------------------------------------------------- host-sync-in-hot-loop
HOST_SYNC_BAD = """
    import jax
    import numpy as np

    def run(xs):
        def body(carry, x):
            v = float(x)
            h = np.asarray(carry)
            return carry + x, v + h.sum()
        return jax.lax.scan(body, 0.0, xs)
"""

HOST_SYNC_GOOD = """
    import jax
    import numpy as np

    def run(xs):
        def body(carry, x):
            return carry + x, x
        r, ys = jax.lax.scan(body, 0.0, xs)
        return float(r), np.asarray(ys)  # host reads OUTSIDE the traced body
"""


def test_host_sync_in_scan_body_is_flagged():
    found = _lint(HOST_SYNC_BAD)
    assert _rules_of(found) == {"host-sync-in-hot-loop"}
    assert len(found) == 2  # float(traced) and np.asarray(traced)


def test_host_reads_outside_body_are_clean():
    assert _lint(HOST_SYNC_GOOD) == []


# -------------------------------------------------------- nonhashable-jit-static
JIT_STATIC_BAD = """
    import jax
    from functools import partial

    @partial(jax.jit, static_argnames=("shape",))
    def reshape(x, shape):
        return x.reshape(shape)

    def run(x):
        a = reshape(x, shape=[4, 2])
        outs = []
        for i in range(8):
            outs.append(reshape(x, shape=(i, 2)))
        return a, outs
"""

JIT_STATIC_GOOD = """
    import jax
    from functools import partial

    @partial(jax.jit, static_argnames=("shape",))
    def reshape(x, shape):
        return x.reshape(shape)

    def run(x):
        return reshape(x, shape=(4, 2))
"""


def test_unhashable_and_varying_statics_are_flagged():
    found = _lint(JIT_STATIC_BAD)
    assert _rules_of(found) == {"nonhashable-jit-static"}
    msgs = " ".join(f.message for f in found)
    assert "hashable" in msgs  # the [4, 2] list literal
    assert "loop variable" in msgs  # shape=(i, 2) in the range() loop


def test_hashable_constant_static_is_clean():
    assert _lint(JIT_STATIC_GOOD) == []


# --------------------------------------------------- donation-use-after-dispatch
DONATION_BAD = """
    import jax

    step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

    def advance(buf, upd):
        out = step(buf, upd)
        total = buf.sum()
        return out, total
"""

DONATION_GOOD = """
    import jax

    step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

    def advance(buf, upd):
        buf = step(buf, upd)  # rebound: the dead buffer is never read
        total = buf.sum()
        return buf, total
"""


def test_read_of_donated_buffer_is_flagged():
    found = _lint(DONATION_BAD)
    assert _rules_of(found) == {"donation-use-after-dispatch"}
    assert any("'buf'" in f.message for f in found)


def test_rebound_donated_buffer_is_clean():
    assert _lint(DONATION_GOOD) == []


# -------------------------------------------------------------- impure-scan-body
IMPURE_SCAN_BAD = """
    import jax

    def run(xs, log):
        def body(carry, x):
            log.append(x)
            print(carry)
            return carry + x, x
        return jax.lax.scan(body, 0.0, xs)
"""

IMPURE_SCAN_GOOD = """
    import jax
    import jax.numpy as jnp

    def run(xs):
        def body(carry, x):
            y = carry.at[0].add(x)  # functional update, not mutation
            jax.debug.print("{x}", x=x)
            return y, x
        return jax.lax.scan(body, jnp.zeros(3), xs)
"""


def test_impure_scan_body_is_flagged():
    found = _lint(IMPURE_SCAN_BAD)
    assert _rules_of(found) == {"impure-scan-body"}
    assert len(found) == 2  # log.append and print


def test_functional_scan_body_is_clean():
    assert _lint(IMPURE_SCAN_GOOD) == []


# ------------------------------------------------------- unvalidated-capacity-mask
# the PR 9 fault-lifecycle class: capacity minus usage ships a negative
# residual once a capacity fault collapses c below what jobs already hold
CAPACITY_MASK_BAD = """
    import jax.numpy as jnp

    def residual(spec, held, c_t):
        used = held.sum(axis=0)
        free = c_t - used
        cap_left = spec.c - jnp.einsum("lrk->rk", held)
        return free / jnp.maximum(cap_left, 1e-9)
"""

CAPACITY_MASK_GOOD = """
    import jax.numpy as jnp

    def residual(spec, held, c_t):
        used = held.sum(axis=0)
        free = jnp.maximum(c_t - used, 0.0)
        cap_left = jnp.clip(spec.c - jnp.einsum("lrk->rk", held), 0.0)
        feasible = (c_t - used >= -1e-4).all()  # checks READ the sign only
        assert c_t.shape == used.shape
        return jnp.where(feasible, free, cap_left)
"""


def test_unguarded_capacity_residual_is_flagged():
    found = _lint(CAPACITY_MASK_BAD)
    assert _rules_of(found) == {"unvalidated-capacity-mask"}
    assert len(found) == 2  # c_t - used and spec.c - ...
    msgs = " ".join(f.message for f in found)
    assert "c_t" in msgs and "c" in msgs


def test_clipped_residual_and_feasibility_check_are_clean():
    assert _lint(CAPACITY_MASK_GOOD) == []


def test_capacity_subtraction_of_constant_is_clean():
    # c - 1.0 is a shift, not a residual against tracked usage
    assert _lint("def f(c):\n    return c - 1.0\n") == []


# --------------------------------------------------------------- hardcoded-tiling
# the PR 10 class: a tile constant spelled outside kernels/autotune.py is a
# knob the autotuner cannot see (how the PR 4 hand-picked ROW_BLOCK = 8
# survived four releases past its sell-by date)
TILING_BAD = """
    from jax.experimental import pallas as pl

    ROW_BLOCK = 8
    FLASH_BLOCK_Q = 128
    TILE_SHAPES = (8, 16, 32)

    def call(kernel, zp, Lp):
        return pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec((64, Lp), lambda i: (i, 0))],
        )(zp)
"""

TILING_GOOD = """
    from jax.experimental import pallas as pl

    from repro.kernels import autotune

    ROW_BLOCK = autotune.SUBLANE_FLOOR       # reference, not a literal
    MULTICLASS_ITERS = 24                    # a solver knob, not a tile

    def call(kernel, zp, rb, Lp):
        return pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec((rb, Lp), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 1, rb, Lp), lambda i: (0, 0, i, 0)),
        )(zp)
"""


def test_hardcoded_tiling_fixture_is_flagged():
    found = _lint(TILING_BAD)
    assert _rules_of(found) == {"hardcoded-tiling"}
    # ROW_BLOCK, FLASH_BLOCK_Q, TILE_SHAPES + the BlockSpec 64
    assert len(found) == 4
    msgs = " ".join(f.message for f in found)
    assert "autotune" in msgs


def test_autotune_references_and_blockspec_vars_are_clean():
    assert _lint(TILING_GOOD) == []


def test_tiling_literals_allowed_in_autotune_home():
    src = "ROW_BLOCKS = (8, 16, 32, 64, 128)\nLANE_FLOOR = 128\n"
    assert lint_source(src, "src/repro/kernels/autotune.py") == []
    assert len(lint_source(src, "src/repro/kernels/oga_step.py")) == 2


def test_hardcoded_tiling_suppression_budget():
    """At most ONE reviewed hardcoded-tiling suppression repo-wide (the
    Pallas lane-width floor carve-out)."""
    import os
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for d in ("src", "benchmarks"):
        for root, _, files in os.walk(os.path.join(repo, d)):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                with open(os.path.join(root, fn), encoding="utf-8") as f:
                    for i, ln in enumerate(f, 1):
                        if re.search(
                            r"lint:\s*disable=.*hardcoded-tiling", ln
                        ):
                            hits.append(f"{fn}:{i}")
    assert len(hits) <= 1, hits


# ------------------------------------------------------------------ suppression
def test_same_line_suppression():
    src = SEED_OFFSET_BAD.replace(
        "np.random.default_rng(seed + 1)",
        "np.random.default_rng(seed + 1)  # lint: disable=rng-offset-derivation",
    ).replace("jax.random.PRNGKey(2 * seed)", "jax.random.PRNGKey(seed)")
    assert _lint(src) == []


def test_preceding_comment_line_suppression():
    src = SEED_OFFSET_BAD.replace(
        "spec = np.random.default_rng(seed + 1)",
        "# lint: disable=rng-offset-derivation\n"
        "        spec = np.random.default_rng(seed + 1)",
    ).replace("jax.random.PRNGKey(2 * seed)", "jax.random.PRNGKey(seed)")
    assert _lint(src) == []


def test_disable_all_and_wrong_rule():
    src = SEED_OFFSET_BAD.replace(
        "jax.random.PRNGKey(2 * seed)", "jax.random.PRNGKey(seed)"
    )
    line = "np.random.default_rng(seed + 1)"
    allsrc = src.replace(line, line + "  # lint: disable=all")
    assert _lint(allsrc) == []
    wrong = src.replace(line, line + "  # lint: disable=torn-publish")
    assert "rng-offset-derivation" in _rules_of(_lint(wrong))


def test_skip_file():
    src = "# lint: skip-file\n" + textwrap.dedent(SEED_OFFSET_BAD)
    assert lint_source(src, "fixture.py") == []


def test_syntax_error_is_a_finding_not_a_crash():
    found = lint_source("def f(:\n", "broken.py")
    assert [f.rule for f in found] == ["syntax-error"]


# ------------------------------------------------------------- registry and API
def test_at_least_ten_rules_registered():
    assert len(RULES) >= 10
    expected = {
        "aliased-buffer-dispatch",
        "rng-offset-derivation",
        "torn-publish",
        "sort-in-loop",
        "host-sync-in-hot-loop",
        "nonhashable-jit-static",
        "donation-use-after-dispatch",
        "impure-scan-body",
        "unvalidated-capacity-mask",
        "hardcoded-tiling",
    }
    assert expected <= set(RULES)


def test_reporters():
    found = _lint(SEED_OFFSET_BAD)
    text = render_text(found)
    assert "rng-offset-derivation" in text
    assert "finding" in text
    assert "clean: no findings" in render_text([])
    report = json.loads(render_json(found, ["fixture.py"]))
    assert report["count"] == len(found)
    assert report["findings"][0]["rule"] == "rng-offset-derivation"
    assert "rng-offset-derivation" in report["rules"]


def test_cli_exit_codes_and_json_out(tmp_path, capsys):
    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent(SEED_OFFSET_GOOD))
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(SEED_OFFSET_BAD))
    assert cli.main([str(good)]) == 0
    report = tmp_path / "report.json"
    assert cli.main([str(bad), "--json-out", str(report)]) == 1
    out = capsys.readouterr().out
    assert "rng-offset-derivation" in out
    data = json.loads(report.read_text())
    assert data["count"] == 2
    assert cli.main([str(bad), "--rule", "torn-publish"]) == 0  # rule filter
    assert cli.main([str(bad), "--rule", "no-such-rule"]) == 2
    assert cli.main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    assert listing.count("\n") >= 8


# --------------------------------------------------------- repo-clean self-test
def test_repository_lints_clean():
    """The permanent guard: the linter must exit clean on its own repo.

    A failure here means either a genuine new instance of a known bug
    class (fix it) or a rule false positive (fix the rule); intentional
    exceptions carry reviewed inline suppressions.
    """
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(repo, d) for d in ("src", "tests", "benchmarks")]
    findings = lint_paths(paths)
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)
