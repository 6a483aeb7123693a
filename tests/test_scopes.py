"""Device scopes (docs/observability.md, "Span and scope vocabulary"): every
operation of the level programs, the margin update and the binning program
that touches a row-sized array carries exactly one ``jax.named_scope`` of the
table in its ``op_name``, so that any profile can be summed by them
(telemetry/xplane.py).  Read from the compiled HLO text at toy shapes, with
the histogram forced to the XLA matmul formulation the chip runs."""
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import xgboost_tpu as xtb
from xgboost_tpu.telemetry import xplane
from xgboost_tpu.tree import grow

R, F, B, DEPTH = 4096, 5, 8, 3  # no node table reaches R elements

# instructions the compiler makes, which carry no op_name of the program
STRUCTURAL = {"parameter", "tuple", "get-tuple-element", "while", "call",
              "conditional", "constant", "broadcast", "bitcast", "copy"}


def row_sized(line: str) -> bool:
    return any(np.prod([int(x) for x in dims.split(",")]) >= R
               for dims in re.findall(r"\[([0-9,]+)\]", line))


def scopes_of_row_sized(hlo: str) -> dict:
    """{scope: count} over the row-sized instructions of ``hlo``; fails on
    one that names no scope, or more than one."""
    seen = {}
    for line in hlo.splitlines():
        opcode = re.search(r" = \S+ ([a-z\-]+)\(", line)
        if " = " not in line or not opcode or not row_sized(line):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        if opcode.group(1) == "parameter" or (
                name is None and opcode.group(1) in STRUCTURAL):
            continue
        assert name is not None, f"row-sized and unnamed: {line[:200]}"
        inside = [p for p in name.group(1).split("/") if p in xplane.SCOPES]
        assert len(inside) == 1, (
            f"op_name {name.group(1)!r} is under {inside or 'no scope'}: "
            f"{line[:200]}")
        seen[inside[0]] = seen.get(inside[0], 0) + 1
    return seen


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("XTB_HIST_IMPL", "matmul")
    rng = np.random.default_rng(0)
    bst = xtb.Booster({"max_depth": DEPTH})
    bst._configure()
    gpair = jnp.asarray(rng.normal(size=(R, 2)).astype(np.float32))
    args = dict(
        bins=jnp.asarray(rng.integers(0, B, size=(R, F)).astype(np.uint8)),
        gpair=gpair,
        cuts=jnp.asarray(np.sort(rng.normal(size=(F, B)), axis=1)
                         .astype(np.float32)),
        nb=jnp.full(F, B, jnp.int32), ones=jnp.ones((1, F), bool),
        setm=jnp.ones((1, F), bool), cm=jnp.zeros(F, bool),
        params=bst._split_params,
        state=grow.init_tree_state(gpair, jnp.ones(R, bool),
                                   max_nodes=grow.max_nodes_for_depth(DEPTH),
                                   n_bin=B))
    yield args
    mp.undo()


def level_hlo(toy, depth, last, subtract, padded) -> str:
    a = toy
    head = (a["state"], a["bins"], a["gpair"], a["cuts"], a["nb"], a["ones"],
            a["setm"], a["cm"])
    if padded:
        width = 1 << (DEPTH - 1)
        prev = jnp.zeros((width, F, B, 2), jnp.float32)
        low = grow.level_step_padded.lower(
            *head, prev, (1 << depth) - 1, None, width=width,
            params=a["params"], subtract=subtract)
    else:
        prev = (jnp.zeros((1 << (depth - 1), F, B, 2), jnp.float32)
                if subtract else None)
        low = grow.level_step.lower(
            *head, prev, None, depth=depth, params=a["params"],
            last_level=last, subtract=subtract)
    return low.compile().as_text()


@pytest.mark.parametrize("depth,last,subtract,padded", [
    (0, False, False, False),      # the root
    (1, False, False, False),      # an interior level, every node built
    (2, False, True, False),       # an interior level, siblings subtracted
    (1, False, True, True),        # the shared padded program
    (2, False, False, True),       # the same with every node built
    (DEPTH, True, False, False),   # leaf finalize: no row-sized work at all
])
def test_level_programs_name_every_row_sized_operation(toy, depth, last,
                                                       subtract, padded):
    seen = scopes_of_row_sized(level_hlo(toy, depth, last, subtract, padded))
    if last:
        assert seen == {}
    else:
        assert set(seen) == {"hist", "route"}, seen


def row_sized_gathers(hlo: str, scope: str) -> list:
    """The gathers under ``scope`` whose output is row-sized."""
    found = []
    for line in hlo.splitlines():
        made = re.search(r" = (\S+) gather\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if (made and row_sized(made.group(1)) and name
                and scope in name.group(1).split("/")):
            found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("depth,subtract,padded", [
    (0, False, False), (2, True, False), (1, True, True), (2, False, True)])
def test_route_gathers_nothing_row_sized(toy, depth, subtract, padded):
    """Beside the matmul histogram the position rewrite is the dense form
    (tree/grow.py): on the chip its gathers were 42% and 52% of a round."""
    hlo = level_hlo(toy, depth, False, subtract, padded)
    assert "route" in scopes_of_row_sized(hlo)
    assert not row_sized_gathers(hlo, "route")


def test_a_row_sized_gather_would_be_seen(toy):
    """The same reading finds the gather form's gathers: the page's and the
    node tables'."""
    best = types.SimpleNamespace(
        feature=jnp.zeros(4, jnp.int32), bin=jnp.zeros(4, jnp.int32),
        default_left=jnp.ones(4, bool))

    def route(bins, pos):
        with jax.named_scope("route"):
            return grow._update_positions_gather(
                bins, pos, best, jnp.ones(4, bool), 3, 4, B, False)

    hlo = jax.jit(route).lower(toy["bins"], toy["state"].pos).compile().as_text()
    assert len(row_sized_gathers(hlo, "route")) >= 2


def test_level_program_names_its_node_work_too(toy):
    """Node-sized work is under ``split`` and ``record``: nothing of a level
    program is left to the unscoped row but what the compiler made."""
    hlo = level_hlo(toy, 1, False, True, True)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    by_scope = {}
    for n in names:
        by_scope[xplane.scope_of(n)] = by_scope.get(xplane.scope_of(n), 0) + 1
    assert {"hist", "split", "record", "route"} <= set(by_scope)
    loose = {n for n in names if xplane.scope_of(n) == xplane.UNSCOPED
             and n.startswith("jit(")}
    assert not loose, f"traced operations outside every scope: {sorted(loose)}"


def bestfirst_hlo(toy, finish=False) -> str:
    from xgboost_tpu.tree import bestfirst

    a = toy
    grower = bestfirst.BestFirstGrower(0, a["params"], max_leaves=12)
    state = bestfirst._init_state(
        jnp.zeros(R, jnp.int32), jnp.zeros(2, jnp.float32),
        S=grower._grow_slots, F=F, B=B, n_sets=1)
    assert grower._grow_slots < R  # no slot table reaches R elements
    if finish:
        return bestfirst._finish.lower(
            state, n_slots=grower.n_slots).compile().as_text()
    return bestfirst.level_step_bestfirst.lower(
        state, a["bins"], a["gpair"], a["nb"], a["ones"],
        jnp.ones((1, 2, F), bool), a["setm"], a["cm"], pairs=grower.pairs,
        max_leaves=12, max_depth=0, gamma_eps=1e-6, params=a["params"],
        has_cat=False, monotone=False).compile().as_text()


def test_bestfirst_pass_names_its_work_and_gathers_nothing_row_sized(toy):
    """The best-first pass (tree/bestfirst.py): its row-sized work is the
    route and the histogram; its node work the queue's two ends, the split
    scan and the block of slots written; nothing traced is left unscoped."""
    hlo = bestfirst_hlo(toy)
    # (the slots' histograms, written under ``record``, outgrow R at toy size)
    assert {"hist", "route"} <= set(scopes_of_row_sized(hlo)) <= {
        "hist", "route", "record"}
    assert not row_sized_gathers(hlo, "route")
    names = re.findall(r'op_name="([^"]*)"', hlo)
    assert {"queue", "route", "hist", "split", "record"} <= {
        xplane.scope_of(n) for n in names}
    loose = {n for n in names if xplane.scope_of(n) == xplane.UNSCOPED
             and n.startswith("jit(")}
    assert not loose, f"traced operations outside every scope: {sorted(loose)}"
    # (the compiler's own tree of the select's reduce carries no op_name)
    finish = bestfirst_hlo(toy, finish=True)
    assert "route" in {xplane.scope_of(n) for n in
                       re.findall(r'op_name="([^"]*)"', finish)}
    assert not row_sized_gathers(finish, "route")


def test_margin_update_is_scoped(toy):
    st = toy["state"]
    hlo = grow.leaf_margin_delta.lower(st.pos, st.leaf_val).compile().as_text()
    assert set(scopes_of_row_sized(hlo)) == {"margin"}


def test_ranking_gradient_is_scoped_and_gathers_only_on_the_way_back():
    """The jitted top-k gradient (objective/ranking.py): every traced
    operation under ``gradient``; the scores reach the grid as slices, so
    the only gathers are the grid's way back to row order."""
    from xgboost_tpu.objective.ranking import (_lambda_gradients_topk,
                                               make_topk_layout)

    rng = np.random.default_rng(2)
    sizes = rng.integers(1, 200, 64)
    sizes[0] = 3 * R  # one group wider than the row-sized threshold
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    y = rng.integers(0, 5, ptr[-1]).astype(np.float32)
    hlo = _lambda_gradients_topk.lower(
        jnp.zeros(int(ptr[-1]) + 7, jnp.float32), make_topk_layout(ptr, y, 32),
        k=32, ndcg_weight=True, score_norm=True, group_norm=True
    ).compile().as_text()
    # (the compiler's own rewrites of the pair block carry no op_name at all)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    assert len(names) > 50
    assert {xplane.scope_of(n) for n in names if n.startswith("jit(")} == {
        "gradient"}
    element = [line for line in hlo.splitlines()
               if re.search(r" gather\(.*slice_sizes=\{1\}", line)]
    assert 1 <= len(element) <= 2
    assert all(f" = f32[{ptr[-1]}" in g for g in element)


def test_binning_program_is_scoped(monkeypatch):
    """``_bin`` is a closure of build_ellpack, jitted there: take it as it is
    handed to jax.jit (the CPU's native binning kernel switched off)."""
    from xgboost_tpu.data import ellpack
    from xgboost_tpu.data.quantile import sketch_dense
    from xgboost_tpu.utils import native

    X = np.random.default_rng(1).normal(size=(R, F)).astype(np.float32)
    jitted = []
    real_jit = jax.jit

    def spy(fn, *a, **kw):
        out = real_jit(fn, *a, **kw)
        if getattr(fn, "__name__", "") == "_bin":
            jitted.append(out)
        return out

    monkeypatch.setattr(native, "ellpack_bin_native", lambda *a, **kw: None)
    monkeypatch.setattr(jax, "jit", spy)
    ellpack.build_ellpack(X, sketch_dense(X, B, use_device=False))
    monkeypatch.setattr(jax, "jit", real_jit)
    assert len(jitted) == 1
    hlo = jitted[0].lower(jnp.asarray(X)).compile().as_text()
    assert set(scopes_of_row_sized(hlo)) == {"bin"}


def test_predict_walkers_are_scoped(monkeypatch):
    """The two entry points eval.predict reaches during training (traced
    with the CPU's native predictor switched off, as on the chip)."""
    from xgboost_tpu.ops import predict

    monkeypatch.setattr(predict, "_native_predict_ok", lambda: False)

    T, M = 2, 7
    tree = dict(feat=jnp.zeros((T, M), jnp.int32), dleft=jnp.ones((T, M), bool),
                left=jnp.zeros((T, M), jnp.int32),
                right=jnp.zeros((T, M), jnp.int32),
                value=jnp.zeros((T, M), jnp.float32),
                groups=jnp.zeros(T, jnp.int32))
    raw = predict.predict_margin_delta.lower(
        jnp.zeros((R, F), jnp.float32), tree["feat"],
        jnp.zeros((T, M), jnp.float32), tree["dleft"], tree["left"],
        tree["right"], tree["value"], tree["groups"], n_groups=1,
        depth=2).compile().as_text()
    binned = predict.predict_margin_delta_binned.lower(
        jnp.zeros((R, F), jnp.uint8), tree["feat"],
        jnp.zeros((T, M), jnp.int32), tree["dleft"], tree["left"],
        tree["right"], tree["value"], tree["groups"], n_groups=1, depth=2,
        n_bin=B).compile().as_text()
    for hlo in (raw, binned):
        assert set(scopes_of_row_sized(hlo)) == {"predict"}
