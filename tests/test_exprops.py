"""Fused basis-matrix lowering (core/exprops.py) tests.

Pins the equivalences the fused engine's speed claims rest on:

  * ``exprops.simplify`` preserves ``Expr.eval`` semantics EXACTLY on
    integer trees (seeded random trees over every node type, plus the
    hypothesis-driven version when installed) and to rounding noise on
    float trees;
  * a ``BasisProgram``'s property columns / GEMV scores match the
    per-property interpreted evaluation;
  * fused ``PlanSpace.scores`` ≡ the PR 3 column engine ≡ the per-plan
    interpreted loop (rtol ≤ 1e-9);
  * streamed-chunk top-k ≡ the full ``rank`` prefix (with and without HBM
    pruning), and ``rank``'s lexsort ordering ≡ the Python tuple-key sort;
  * incremental (``BasisCache``) rescores ≡ cold rescores, and a
    device-count delta reuses ≥ half of the basis columns;
  * the persistent compile cache: a second build with the same key skips
    the builder and the loaded program scores identically.

Plus the satellites: cached ``Expr`` repr/hash (no re-walk on repeat
probes), warm/cold disk-cache reporting.
"""
from __future__ import annotations

import json
import os
import random

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS
from repro.core import exprops, planspace, predictor
from repro.core.model import LinearCostModel
from repro.core.symcount import (
    Add, CeilDiv, Const, Expr, FloorDiv, Max, Min, Mul, Piecewise, Pow,
    Var, compile_vector, evaluate_vector,
)
from repro.launch.autoshard import candidate_plans

_VARS = ("x", "y", "z")


# ---------------------------------------------------------------------------
# simplify ≡ eval (property-based)
# ---------------------------------------------------------------------------


def random_int_expr(rng: random.Random, depth: int) -> Expr:
    """Random trees over every node type with INTEGER constants only, so
    Python's arbitrary-precision arithmetic makes ``simplify`` exact."""
    if depth <= 0 or rng.random() < 0.25:
        if rng.random() < 0.55:
            return Var(rng.choice(_VARS))
        return Const(rng.randint(-4, 6))
    op = rng.randrange(9)
    a = random_int_expr(rng, depth - 1)
    b = random_int_expr(rng, depth - 1)
    if op == 0:
        return Add(a, b)
    if op == 1:
        return Mul(a, b)
    if op == 2:
        return a - b
    if op == 3:
        return FloorDiv(a, Const(rng.randint(1, 5)))
    if op == 4:
        return CeilDiv(a, Const(rng.randint(1, 5)))
    if op == 5:
        return Max(a, b) if rng.random() < 0.5 else Min(a, b)
    if op == 6:
        return Piecewise([(a, b)], random_int_expr(rng, depth - 1))
    if op == 7:
        return Piecewise([(Const(rng.randint(-1, 1)), a)], b)
    return Pow(a, rng.choice((0, 1, 2)))


def _check_simplify_matches_eval(seed: int) -> None:
    rng = random.Random(seed)
    e = random_int_expr(rng, depth=4)
    s = exprops.simplify(e)
    for _ in range(8):
        env = {v: rng.randint(-5, 12) for v in _VARS}
        assert s.eval(env) == e.eval(env), (e, s, env)


@pytest.mark.parametrize("seed", range(60))
def test_simplify_matches_eval_random_trees(seed):
    _check_simplify_matches_eval(seed)


@given(st.integers(min_value=0, max_value=2 ** 32 - 1))
@settings(max_examples=200, deadline=None)
def test_simplify_matches_eval_hypothesis(seed):
    _check_simplify_matches_eval(seed)


@pytest.mark.parametrize("seed", range(20))
def test_simplify_float_trees_close(seed):
    """Float constants may reassociate under canonicalization — pinned to
    rounding noise, mirroring the engine's 1e-9 score equivalence bar."""
    rng = random.Random(seed)

    def rand_float_expr(depth):
        if depth <= 0 or rng.random() < 0.3:
            return Var(rng.choice(_VARS)) if rng.random() < 0.5 \
                else Const(round(rng.uniform(-2.0, 3.0), 3))
        a, b = rand_float_expr(depth - 1), rand_float_expr(depth - 1)
        return rng.choice((Add(a, b), Mul(a, b), a - b, Max(a, b),
                           Min(a, b)))

    e = rand_float_expr(4)
    s = exprops.simplify(e)
    for _ in range(8):
        env = {v: rng.randint(1, 9) for v in _VARS}
        assert s.eval(env) == pytest.approx(e.eval(env), rel=1e-12, abs=1e-9)


def test_simplify_canonical_rewrites():
    x, y = Var("x"), Var("y")
    # constant folding + like-term collection
    assert repr(exprops.simplify((x + 0) * 1 + x + 2 * x + Const(3)
                                 + Const(4))) == "(4*x + 7)"
    # zero annihilation and Pow identities
    assert repr(exprops.simplify(Mul(Const(0), x) + Pow(x, 1))) == "x"
    assert repr(exprops.simplify(Pow(x, 0))) == "1"
    # constant distributes over a sum so shared addends stay visible
    assert repr(exprops.simplify(2 * (x + y))) == "(2*x + 2*y)"
    # Max flattening, dedup, constant pre-fold
    m = exprops.simplify(Max(Max(x, Const(2)), x, Const(5)))
    assert repr(m) == "max(5, x)"
    # Piecewise: else-chain hoisting + constant-guard resolution
    pw = Piecewise([(x - 1, y)], Piecewise([(Const(2), Const(7))],
                                           Const(9)))
    s = exprops.simplify(pw)
    assert isinstance(s, Piecewise) and len(s.branches) == 1
    assert repr(s.otherwise) == "7"     # const guard 2>0 always fires
    # dead constant guard drops its branch entirely
    assert repr(exprops.simplify(Piecewise([(Const(0), x)], y))) == "y"
    # a branch whose value equals the fallthrough is dropped
    assert repr(exprops.simplify(Piecewise([(x, y)], y))) == "y"


# ---------------------------------------------------------------------------
# BasisProgram ≡ interpreted property evaluation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(25))
def test_program_columns_match_interpreted(seed):
    rng = random.Random(seed)
    pv = {f"p{i}": random_int_expr(rng, 3) for i in range(5)}
    pv["p_const"] = 3.5
    prog = exprops.build_program(pv)
    n = 16
    env = {v: np.asarray([rng.randint(1, 24) for _ in range(n)],
                         dtype=np.int64) for v in _VARS}
    cols = prog.property_columns(env, n)
    for k, v in pv.items():
        ref = [float(v.eval({vn: int(env[vn][i]) for vn in _VARS}))
               if isinstance(v, Expr) else float(v) for i in range(n)]
        np.testing.assert_allclose(cols[k], ref, rtol=1e-9, atol=1e-9,
                                   err_msg=k)
    # GEMV score ≡ weighted interpreted sum, cached and uncached
    model = LinearCostModel.from_dict(
        {k: rng.uniform(0.5, 2.0) for k in pv})
    ref = np.zeros(n)
    for k, w in zip(model.keys, model.weights):
        ref += w * np.asarray(cols[k])
    np.testing.assert_allclose(
        exprops.score_cells(prog, env, n, model), ref, rtol=1e-9)
    cache = exprops.BasisCache()
    np.testing.assert_allclose(
        exprops.score_cells(prog, env, n, model, cache), ref, rtol=1e-9)
    np.testing.assert_allclose(                      # warm pass
        exprops.score_cells(prog, env, n, model, cache), ref, rtol=1e-9)
    assert cache.hits > 0
    # basis matrix: B @ Cᵀ + const reproduces every property column
    B = prog.matrix(env, n)
    assert B.shape == (n, prog.n_terms)
    P = B @ prog.coeff.T + prog.const
    for j, k in enumerate(prog.keys):
        np.testing.assert_allclose(P[:, j], cols[k], rtol=1e-12)


def test_program_json_roundtrip_scores_identically():
    rng = random.Random(7)
    pv = {f"p{i}": random_int_expr(rng, 3) for i in range(4)}
    prog = exprops.build_program(pv)
    clone = exprops.BasisProgram.from_json_dict(
        json.loads(json.dumps(prog.to_json_dict())))
    model = LinearCostModel.from_dict({k: 1.25 for k in pv})
    n = 8
    env = {v: np.arange(1, n + 1, dtype=np.int64) for v in _VARS}
    np.testing.assert_array_equal(
        exprops.score_cells(prog, env, n, model),
        exprops.score_cells(clone, env, n, model))
    # the cached per-term path works on a loaded program too (term lambdas
    # rebuild from their serialized sources)
    np.testing.assert_allclose(
        exprops.score_cells(clone, env, n, model, exprops.BasisCache()),
        exprops.score_cells(prog, env, n, model), rtol=1e-12)


def test_program_stale_format_rejected():
    d = exprops.build_program({"p": Var("x")}).to_json_dict()
    d["format"] = -1
    with pytest.raises(ValueError):
        exprops.BasisProgram.from_json_dict(d)


# ---------------------------------------------------------------------------
# fused ≡ columns ≡ interpreted loop goldens
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sweep_cell():
    cfg = ARCHS["smollm-360m"]
    shape = SHAPES["train_4k"]
    plans = candidate_plans(cfg, shape)
    meshes = planspace.mesh_factorizations(64) \
        + planspace.mesh_factorizations(48)
    return cfg, shape, plans, meshes


def test_fused_scores_match_columns_and_loop(sweep_cell):
    cfg, shape, plans, meshes = sweep_cell
    space = planspace.PlanSpace.from_product(cfg, shape, plans, meshes)
    fused = space.scores(None)
    cols = space.scores_columns(None)
    np.testing.assert_allclose(fused, cols, rtol=1e-9)
    loop = np.concatenate([
        predictor.predict_plans_loop(cfg, shape, plans, m) for m in meshes])
    np.testing.assert_allclose(
        fused.reshape(len(plans), len(meshes)),
        loop.reshape(len(meshes), len(plans)).T, rtol=1e-9)


def test_fused_step_program_matches_compiled_vector(sweep_cell):
    cfg, shape, plans, _ = sweep_cell
    from repro.core.workload import WorkloadSpec
    prog = predictor.step_program(cfg, WorkloadSpec(phase="train"), "full")
    cv = predictor.step_vector_fn(cfg, WorkloadSpec(phase="train"), "full")
    env = {"B": shape.global_batch, "S": shape.seq_len,
           "M": np.asarray([1, 2, 4, 8], dtype=np.int64)}
    model = predictor.resolve_model(None)
    ref = np.zeros(4)
    w = dict(zip(model.keys, model.weights))
    for k, v in cv(env).items():
        if w.get(k):
            ref += w[k] * np.broadcast_to(
                np.asarray(v, dtype=np.float64), (4,))
    np.testing.assert_allclose(
        exprops.score_cells(prog, env, 4, model), ref, rtol=1e-9)


# ---------------------------------------------------------------------------
# rank: lexsort ordering + argpartition top-k ≡ the tuple-key reference
# ---------------------------------------------------------------------------


def _reference_rank(space, model):
    secs = space.scores(model)
    order = sorted(range(len(space)),
                   key=lambda i: (secs[i],
                                  planspace.plan_sort_key(space.plans[i]),
                                  planspace.mesh_sort_key(
                                      space.mesh_shapes[i])))
    return [(float(secs[i]), space.plans[i], space.mesh_shapes[i])
            for i in order]


@pytest.mark.parametrize("model_kind", ["seed", "flat"])
def test_rank_lexsort_matches_tuple_sort(sweep_cell, model_kind):
    cfg, shape, plans, meshes = sweep_cell
    space = planspace.PlanSpace.from_product(cfg, shape, plans[:40], meshes)
    # "flat" scores every cell identically, exercising pure tie-breaks
    model = None if model_kind == "seed" else LinearCostModel(
        keys=["const1"], weights=np.array([1.0]), device="flat")
    assert space.rank(model) == _reference_rank(space, model)


def test_rank_topk_is_full_prefix(sweep_cell):
    cfg, shape, plans, meshes = sweep_cell
    space = planspace.PlanSpace.from_product(cfg, shape, plans, meshes)
    full = space.rank(None)
    for k in (0, 1, 5, 23, len(space), len(space) + 7):
        assert space.rank(None, top_k=k) == full[:k]


# ---------------------------------------------------------------------------
# streaming: chunked top-k ≡ full rank prefix, bounded pools
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [37, 300, 10 ** 7])
def test_stream_topk_matches_rank_prefix(sweep_cell, chunk):
    cfg, shape, plans, meshes = sweep_cell
    space = planspace.PlanSpace.from_product(cfg, shape, plans, meshes)
    full = space.rank(None)
    for k in (1, 7, 19):
        stats = {}
        got = planspace.stream_topk(cfg, shape, plans, meshes, None, k=k,
                                    chunk_cells=chunk, stats=stats)
        assert got == full[:k]
        assert stats["cells"] == len(space)
        assert stats["max_chunk_cells"] <= max(chunk, len(meshes))
        assert stats["pool_high_water"] <= k + chunk + len(meshes)


def test_stream_topk_hbm_pruning(sweep_cell):
    cfg, shape, plans, meshes = sweep_cell
    space = planspace.PlanSpace.from_product(cfg, shape, plans, meshes)
    budget = float(np.median(space.peak_bytes()))  # force real pruning
    secs = space.scores(None)
    mask = space.feasible_mask(budget)
    order = planspace._rank_order(
        secs, space.plans, space.mesh_shapes)
    expected = [(float(secs[i]), space.plans[i], space.mesh_shapes[i])
                for i in order if mask[i]][:8]
    stats = {}
    got = planspace.stream_topk(cfg, shape, plans, meshes, None, k=8,
                                chunk_cells=256, hbm_budget=budget,
                                stats=stats)
    assert got == expected
    assert stats["pruned_cells"] == int((~mask).sum())


def test_stream_topk_pool_stays_bounded_under_total_ties(sweep_cell):
    """A model blind to the mesh scores every cell identically; tie
    closure alone would retain the whole space.  The pool must stay
    bounded AND the result must still be the exact rank prefix."""
    cfg, shape, plans, meshes = sweep_cell
    flat = LinearCostModel(keys=["const1"], weights=np.array([1.0]),
                           device="flat")
    space = planspace.PlanSpace.from_product(cfg, shape, plans, meshes)
    full = space.rank(flat)
    k = 5
    stats = {}
    got = planspace.stream_topk(cfg, shape, plans, meshes, flat, k=k,
                                chunk_cells=64, stats=stats)
    assert got == full[:k]
    assert stats["pool_high_water"] <= k + 512 + 64 + len(meshes)
    assert stats["pool_high_water"] < len(space) // 2


def test_stream_topk_empty_and_degenerate(sweep_cell):
    cfg, shape, plans, meshes = sweep_cell
    assert planspace.stream_topk(cfg, shape, [], meshes, None, k=3) == []
    assert planspace.stream_topk(cfg, shape, plans, [], None, k=3) == []
    assert planspace.stream_topk(cfg, shape, plans, meshes, None, k=0) == []
    # a budget nothing satisfies yields an empty result, not a crash
    assert planspace.stream_topk(cfg, shape, plans[:4], meshes, None, k=3,
                                 hbm_budget=1.0) == []


# ---------------------------------------------------------------------------
# incremental rescoring (BasisCache)
# ---------------------------------------------------------------------------


def test_incremental_rescore_matches_cold_after_device_delta(sweep_cell):
    cfg, shape, plans, _ = sweep_cell
    model = predictor.resolve_model(None)
    cache = exprops.BasisCache()
    for n_dev in (64, 63):  # second space: a single device-count delta
        meshes = planspace.mesh_factorizations(n_dev)
        cells = [(p, m) for p in plans[:10] for m in meshes]
        space = planspace.PlanSpace.from_cells(cfg, shape, cells)
        cold = space.scores(model)
        warm = space.scores(model, cache=cache)
        np.testing.assert_allclose(warm, cold, rtol=1e-12)
    # the delta only touches DP/TP-keyed columns: ≥ half came from cache
    assert cache.hits >= cache.misses > 0


def test_elastic_replan_reuses_basis_columns(sweep_cell):
    from repro.distributed import elastic
    cfg, shape, _, _ = sweep_cell
    model = predictor.resolve_model(None)
    elastic.replan(cfg, shape, 64, model)
    h0, m0 = elastic._BASIS_CACHE.hits, elastic._BASIS_CACHE.misses
    opts = elastic.replan(cfg, shape, 63, model)
    h1, m1 = elastic._BASIS_CACHE.hits, elastic._BASIS_CACHE.misses
    assert (h1 - h0) >= (m1 - m0), "device delta must reuse >= half"
    # incremental scores stay pinned to the interpreted predictor
    for o in opts:
        ref = predictor.predict_step(cfg, shape, o.plan, o.shape).seconds
        assert o.predicted_step_s == pytest.approx(ref, rel=1e-9)


def test_straggler_monitor_scores_through_cache(sweep_cell):
    from repro.runtime.straggler import StragglerMonitor, _BASIS_CACHE
    cfg, shape, plans, _ = sweep_cell
    mesh = {"data": 8, "model": 8}
    mon = StragglerMonitor.from_model(cfg, shape, plans[0], mesh, n_hosts=4)
    ref = predictor.predict_plans(cfg, shape, [plans[0]], mesh)
    assert mon.predicted_step_s == pytest.approx(float(ref[0]), rel=1e-9)
    probes = _BASIS_CACHE.hits + _BASIS_CACHE.misses
    assert probes > 0


# ---------------------------------------------------------------------------
# persistent on-disk compile cache
# ---------------------------------------------------------------------------


def test_disk_cache_warm_second_build(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_COMPILE_CACHE", str(tmp_path))
    calls = []

    def builder():
        calls.append(1)
        return {"p": Var("x") * 3 + 1, "q": CeilDiv(Var("x"), Const(4))}

    key = exprops.program_key("test-program", "v1")
    p1 = exprops.load_or_build(key, builder)
    p2 = exprops.load_or_build(key, builder)
    assert len(calls) == 1, "second build must come from disk"
    model = LinearCostModel.from_dict({"p": 2.0, "q": 0.5})
    env = {"x": np.arange(1, 9, dtype=np.int64)}
    np.testing.assert_array_equal(exprops.score_cells(p1, env, 8, model),
                                  exprops.score_cells(p2, env, 8, model))
    # a different key is a different program
    other = exprops.program_key("test-program", "v2")
    exprops.load_or_build(other, builder)
    assert len(calls) == 2


def test_disk_cache_disabled_and_report(monkeypatch):
    monkeypatch.setenv("REPRO_COMPILE_CACHE", "off")
    assert exprops.compile_cache_dir() is None
    assert exprops.disk_cache_report() == "compile cache: disabled"
    monkeypatch.setenv("REPRO_COMPILE_CACHE", "/tmp/somewhere")
    assert exprops.compile_cache_dir() == "/tmp/somewhere"
    assert exprops.disk_cache_report().startswith("compile cache:")
    # unset: a fixed directory inside the checkout, beside JAX's cache
    monkeypatch.delenv("REPRO_COMPILE_CACHE")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert exprops.compile_cache_dir() == os.path.join(root, ".cache",
                                                       "exprops")


def test_program_key_changes_with_inputs():
    k1 = exprops.program_key("step", "cfg-a", "train", "full")
    k2 = exprops.program_key("step", "cfg-a", "train", "dots")
    k3 = exprops.program_key("step", "cfg-b", "train", "full")
    assert len({k1, k2, k3}) == 3


# ---------------------------------------------------------------------------
# satellite: cached Expr repr/hash (no tree re-walks on repeat probes)
# ---------------------------------------------------------------------------


def _deep_tree(depth: int) -> Expr:
    e = Var("x")
    f = Var("y")
    for i in range(depth):
        e = Add(Mul(e, Const(2)), f) if i % 2 else Mul(Add(e, f), Const(3))
    return e


def test_expr_hash_does_not_rewalk(monkeypatch):
    e1 = _deep_tree(60)
    e2 = _deep_tree(60)
    h1, h2 = hash(e1), hash(e2)      # populate the repr/hash caches
    assert h1 == h2 and e1 == e2
    calls = {"n": 0}
    orig = Add._render

    def counting(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(Add, "_render", counting)
    assert hash(e1) == h1
    assert repr(e2) and e1 == e2     # equality probes reuse cached reprs
    assert calls["n"] == 0, "hash/eq after first use must not re-serialize"


def test_expr_hash_eq_still_structural():
    a = Add(Var("x"), Const(1))
    b = Add(Var("x"), Const(1))
    c = Add(Var("x"), Const(2))
    assert a == b and hash(a) == hash(b)
    assert a != c
    assert len({a, b, c}) == 2
