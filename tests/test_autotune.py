"""kernels.autotune: the shape-aware tiling cache.

Pins the design contract dispatch relies on:

* ``tune`` is deterministic given a fixed measurement table (ties break
  toward enumeration order), so CI reruns converge on one winner;
* corrupt / stale / torn cache state is a MISS, never a crash or a wrong
  config (same torn-write matrix discipline as tests/test_ckpt);
* ``resolve`` never measures — the warmed dispatch path performs ZERO
  autotune measurements and ZERO misses (the CI kernel-gate invariant);
* a miss answers with ``shape_rule``: deterministic, within the row
  bucket and the VMEM budget, overridden by any tuned entry, and counted;
* winners publish through the atomic ckpt write path (no temp droppings,
  readable table after every store);
* shapes bucket (rows to pow2, lanes to the 128 floor) so neighbouring
  problem sizes share one winner, and the key binds platform + jax
  version so foreign tables are clean misses.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune, ops


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    autotune.reset_cache()
    autotune.reset_stats()
    yield
    autotune.reset_cache()
    autotune.reset_stats()


def _fake_measure(table):
    """Measurement fn from a fixed {(row_block, method, iters): us} table."""
    return lambda cfg: table[(cfg.row_block, cfg.method, cfg.iters)]


# ------------------------------------------------------------- determinism --
def test_tune_is_deterministic_given_fixed_measurements():
    table = {(rb, "sortscan", 0): 1000.0 - rb for rb in autotune.ROW_BLOCKS}
    table[(32, "sortscan", 0)] = 1.0  # the planted winner
    win1, m1 = autotune.tune("oga_step", 256, 10, measure=_fake_measure(table))
    win2, m2 = autotune.tune("oga_step", 256, 10, measure=_fake_measure(table))
    assert win1 == win2 == autotune.KernelConfig(32, "sortscan", 0)
    assert m1 == m2
    # and the stored entry resolves to the same winner
    assert autotune.resolve("oga_step", 256, 10) == win1


def test_tune_ties_break_toward_enumeration_order():
    table = {(rb, "sortscan", 0): 7.0 for rb in autotune.ROW_BLOCKS}
    win, _ = autotune.tune("proj", 256, 10, measure=_fake_measure(table))
    assert win.row_block == autotune.ROW_BLOCKS[0]


def test_tune_store_false_does_not_publish():
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    autotune.tune("proj", 64, 10, measure=_fake_measure(table), store=False)
    assert autotune.lookup("proj", 64, 10) is None
    assert not os.path.exists(autotune.cache_path())


# ---------------------------------------------------------- candidate space --
def test_candidates_cap_row_block_at_row_bucket():
    cands = autotune.candidates("oga_step", 64, 10)
    assert cands and all(c.row_block <= 64 for c in cands)
    assert {c.method for c in cands} == {"sortscan"}


def test_candidates_bisect_enumerates_iters():
    cands = autotune.candidates("proj", 256, 10, methods=("bisect",))
    assert {c.iters for c in cands} == set(autotune.BISECT_ITERS)


def test_candidates_vmem_filter_drops_big_sortscan_tiles():
    cands = autotune.candidates("proj", 4096, 2048)
    assert cands  # never empty
    worst = max(c.row_block for c in cands)
    assert worst < max(autotune.ROW_BLOCKS)  # the filter actually bit
    assert autotune.vmem_bytes(worst, 2048) <= autotune.VMEM_BUDGET


def test_shape_bucketing_shares_winners_between_neighbours():
    # 250 rows x 10 lanes and 256 rows x 120 lanes land in one bucket
    assert autotune.cache_key("proj", 250, 10) == autotune.cache_key("proj", 256, 120)
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    win, _ = autotune.tune("proj", 256, 10, measure=_fake_measure(table))
    assert autotune.resolve("proj", 250, 120) == win
    assert autotune.cache_stats()["hits"] == 1


# -------------------------------------------------- corrupt / stale = miss --
def _write_cache(payload) -> str:
    path = autotune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(payload, str):
            f.write(payload)
        else:
            json.dump(payload, f)
    autotune.reset_cache()
    return path


def _entry(**kw):
    ent = {"row_block": 32, "method": "sortscan", "iters": 0, "us": 1.0}
    ent.update(kw)
    return {"version": autotune.TABLE_VERSION,
            "entries": {autotune.cache_key("proj", 256, 10): ent}}


@pytest.mark.parametrize("payload", [
    "{ not json at all",                                     # garbage bytes
    "",                                                      # truncated empty
    json.dumps(_entry())[:37],                               # torn mid-write
    {"version": autotune.TABLE_VERSION + 1, "entries": {}},  # future schema
    {"entries": "not-a-dict", "version": autotune.TABLE_VERSION},
    [1, 2, 3],                                               # wrong top type
], ids=["garbage", "empty", "torn", "version", "schema", "toptype"])
def test_damaged_table_is_a_miss_not_a_crash(payload):
    _write_cache(payload)
    assert autotune.lookup("proj", 256, 10) is None
    assert autotune.resolve("proj", 256, 10) == autotune.shape_rule(256, 10)
    assert autotune.cache_stats()["misses"] == 1


@pytest.mark.parametrize("ent_kw", [
    {"row_block": 24},          # not a legal tile
    {"row_block": "32"},        # wrong type
    {"method": "quickselect"},  # unknown method
    {"iters": -3},              # out of range
    {"iters": 999},
    {"row_block": None},
], ids=["illegal-rb", "str-rb", "method", "neg-iters", "huge-iters", "none-rb"])
def test_malformed_entry_is_a_miss(ent_kw):
    _write_cache(_entry(**ent_kw))
    assert autotune.lookup("proj", 256, 10) is None
    assert autotune.resolve("proj", 256, 10) == autotune.shape_rule(256, 10)


def test_foreign_platform_or_jax_version_is_a_clean_miss():
    key = "proj|N256xL128|tpu-v9|jax99.0.0"
    _write_cache({"version": autotune.TABLE_VERSION,
                  "entries": {key: {"row_block": 32, "method": "sortscan",
                                    "iters": 0}}})
    assert autotune.lookup("proj", 256, 10) is None


def test_store_recovers_a_torn_table():
    _write_cache("{ torn")
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    win, _ = autotune.tune("proj", 256, 10, measure=_fake_measure(table))
    assert autotune.lookup("proj", 256, 10) == win


# ------------------------------------------------------------ atomic publish --
def test_store_publishes_atomically_no_temp_droppings():
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    autotune.tune("proj", 256, 10, measure=_fake_measure(table))
    autotune.tune("oga_step", 64, 10, measure=_fake_measure(table))
    cache_dir = os.path.dirname(autotune.cache_path())
    assert sorted(os.listdir(cache_dir)) == ["autotune.json"]
    raw = json.load(open(autotune.cache_path()))
    assert raw["version"] == autotune.TABLE_VERSION
    assert len(raw["entries"]) == 2  # second store kept the first entry


# --------------------------------------------- resolve never measures (pin) --
def test_resolve_never_measures_even_on_miss():
    assert (autotune.resolve("oga_step", 512, 24)
            == autotune.shape_rule(512, 24))
    assert autotune.measurement_count() == 0
    assert autotune.cache_stats()["misses"] == 1


def test_warmed_dispatch_path_zero_measurements_zero_misses():
    """The CI kernel-gate invariant: once tuned, production dispatch runs
    entirely off the table — no re-measurement, no fallback configs."""
    N, L = 8, 16
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    autotune.tune("oga_step", N, L, measure=_fake_measure(table))
    autotune.reset_stats()
    ones = jnp.ones((N, L))
    scal = jnp.stack([jnp.full((N,), v) for v in (1.2, 0.4, 5.0, 0.0, 0.5)],
                     axis=1)
    ops.oga_step_fused(ones, ones, ones, ones, ones, scal, use_pallas=True)
    stats = autotune.cache_stats()
    assert stats["measurements"] == 0
    assert stats["misses"] == 0
    assert stats["hits"] >= 1


def test_dispatch_forces_sortscan_even_if_cache_says_bisect():
    """Cache state must never change VALUES, only speed: a (stale) bisect
    winner contributes its row_block, but production dispatch still runs
    the exact sortscan method."""
    N, L = 8, 16
    _write_cache({"version": autotune.TABLE_VERSION,
                  "entries": {autotune.cache_key("oga_step", N, L): {
                      "row_block": 16, "method": "bisect", "iters": 12}}})
    import numpy as np

    from repro.kernels import ref

    ones = jnp.ones((N, L))
    scal = jnp.stack([jnp.full((N,), v) for v in (1.2, 0.4, 5.0, 0.0, 0.5)],
                     axis=1)
    got = ops.oga_step_fused(ones, ones, ones, ones, ones, scal,
                             use_pallas=True)
    want = ref.oga_step_ref(ones, ones, ones, ones, ones, scal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------- env override --
def test_cache_path_honours_env_override(tmp_path):
    assert autotune.cache_path() == str(tmp_path / "autotune.json")


def test_kernel_config_is_hashable_jit_static():
    cfg = autotune.KernelConfig(32, "sortscan", 0)
    assert hash(cfg) == hash(autotune.KernelConfig(32, "sortscan", 0))
    assert cfg.to_dict() == {"row_block": 32, "method": "sortscan", "iters": 0}


def test_unset_cache_env_reads_no_file_and_refuses_to_publish(monkeypatch):
    """Without REPRO_AUTOTUNE_CACHE dispatch runs the shape rule and opens
    no table (none in the home directory either); tune() cannot
    publish a winner nobody would read."""
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE")
    autotune.reset_cache()
    assert autotune.cache_path() is None
    opened = []
    with monkeypatch.context() as m:
        m.setattr("builtins.open", lambda *a, **k: opened.append(a))
        assert (autotune.resolve("oga_step", 4096, 10)
                == autotune.shape_rule(4096, 10))
    assert opened == []
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    with pytest.raises(ValueError, match="REPRO_AUTOTUNE_CACHE"):
        autotune.tune("proj", 64, 10, measure=_fake_measure(table))


# ------------------------------------------------------------- shape rule --
# packed (rows, lanes) of the benchmark's deployments: Tab. 2 over a
# 64-point sweep chunk (64 x 128 x 6 rows of 10 ports), Fig. 5 (1024 x 6
# rows of 100 ports), and a 200-port problem that pads to 256 lanes
TAB2, FIG5, WIDE = (49152, 10), (6144, 100), (4096, 200)
RULE_SHAPES = [(16, 10), (768, 10), TAB2, FIG5, WIDE, (4096, 2048),
               (8, 4000), (3, 1)]


@pytest.mark.parametrize("n,l", RULE_SHAPES)
def test_shape_rule_is_deterministic(n, l):
    first = autotune.shape_rule(n, l)
    # the table's state plays no part: tune another shape in between
    table = {(rb, "sortscan", 0): float(rb) for rb in autotune.ROW_BLOCKS}
    autotune.tune("oga_step", 64, 10, measure=_fake_measure(table))
    assert autotune.shape_rule(n, l) == first
    assert first.row_block in autotune.ROW_BLOCKS
    assert first.method == "sortscan"


@pytest.mark.parametrize("n,l", RULE_SHAPES)
def test_shape_rule_stays_in_row_bucket_and_vmem_budget(n, l):
    rb = autotune.shape_rule(n, l).row_block
    if rb == autotune.ROW_BLOCKS[0]:
        return  # the smallest tile is the floor every shape may take
    assert rb <= autotune.shape_bucket(n, l)[0]
    assert autotune.vmem_bytes(rb, l) <= autotune.VMEM_BUDGET
    assert rb * autotune.sort_lanes(l) <= autotune.SORT_TILE_MAX
    assert -(-n // rb) >= autotune.MIN_GRID_STEPS


# (rows, lanes, the row block the v5e sweep found fastest; PERF.md)
MEASURED_WINNERS = [TAB2 + (256,), FIG5 + (256,), WIDE + (128,),
                    (49152, 200, 128), (12288, 200, 128), (4096, 10, 256),
                    (3072, 10, 256)]


@pytest.mark.parametrize("n,l,best", MEASURED_WINNERS)
def test_shape_rule_picks_the_measured_winners(n, l, best):
    assert autotune.shape_rule(n, l).row_block == best


def test_shape_rule_widens_tab2_tile_over_a_16_row_problem():
    assert (autotune.shape_rule(*TAB2).row_block
            > autotune.shape_rule(16, 10).row_block)


def test_shape_rule_narrows_tiles_when_lanes_widen():
    """At 2048 ports the sort's tile, not the rows, caps the row block."""
    rb = autotune.shape_rule(49152, 2048).row_block
    assert rb < autotune.shape_rule(49152, 10).row_block
    assert rb * autotune.sort_lanes(2048) <= autotune.SORT_TILE_MAX
    wider = [b for b in autotune.ROW_BLOCKS if b > rb]
    assert wider
    assert wider[0] * autotune.sort_lanes(2048) > autotune.SORT_TILE_MAX


# (row block, lanes, KiB of scoped VMEM the v5e compiler reported for the
# fused sortscan step at that tile, compiled for a described v5e)
MEASURED_VMEM_KIB = [
    (32, 100, 104.0), (128, 100, 1108.5), (256, 100, 2552.3),
    (512, 10, 5470.7), (128, 10, 384.0), (256, 200, 5747.2),
    (8, 400, 404.0), (32, 400, 1538.6), (128, 400, 5962.2),
    (8, 800, 1088.0), (32, 800, 2951.7), (8, 1500, 2460.2),
    (16, 1500, 3525.1),
]


@pytest.mark.parametrize("rb,l,kib", MEASURED_VMEM_KIB)
def test_vmem_model_bounds_the_compilers_footprint(rb, l, kib):
    assert autotune.vmem_bytes(rb, l) >= kib * 1024


def test_tuned_entry_wins_over_shape_rule():
    n, l = TAB2
    table = {(rb, "sortscan", 0): 100.0 for rb in autotune.ROW_BLOCKS}
    table[(16, "sortscan", 0)] = 1.0  # planted winner, not the rule's tile
    win, _ = autotune.tune("oga_step", n, l, measure=_fake_measure(table))
    assert win.row_block == 16 != autotune.shape_rule(n, l).row_block
    autotune.reset_stats()
    assert autotune.resolve("oga_step", n, l) == win
    stats = autotune.cache_stats()
    assert (stats["hits"], stats["misses"], stats["rule"]) == (1, 0, 0)
    assert stats["last"] == (16, n, 128)


def test_rule_counter_counts_rule_answers_and_records_the_last_tile():
    assert autotune.cache_stats()["rule"] == 0
    assert autotune.cache_stats()["last"] is None
    autotune.resolve("oga_step", *TAB2)
    autotune.resolve("proj", *WIDE)
    stats = autotune.cache_stats()
    assert (stats["misses"], stats["rule"]) == (2, 2)
    assert stats["last"] == (128, 4096, 256)
    # a kernel called without a row block asks the rule too (at trace time)
    from repro.kernels import sortscan

    z = jax.ShapeDtypeStruct((40, 24), jnp.float32)
    c = jax.ShapeDtypeStruct((40,), jnp.float32)
    jax.eval_shape(lambda *o: sortscan.proj_sortscan(*o, interpret=True),
                   z, z, z, c)
    stats = autotune.cache_stats()
    assert stats["rule"] == 3
    assert stats["last"] == (autotune.shape_rule(40, 24).row_block, 40, 128)
    autotune.reset_stats()
    assert autotune.cache_stats()["rule"] == 0
    assert autotune.cache_stats()["last"] is None
