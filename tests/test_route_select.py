"""A router's pick of k among n (``ops/moe_ops.py`` ``k_largest``): both
forms against ``jax.lax.top_k`` to the bit at every (k, n) the six routed
configurations ask, with ties, a row of NaN and a row of -inf; ``route``
itself against a plain ``numpy.argsort`` reference; and the word the
engine's statistics say it by."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops import moe_ops
from flexflow_tpu.ops.moe_ops import RoutedExperts, k_largest, select_form

# (k, n): ISSUE 62's table of the calls a routed layer makes, a cell a row
CALLS = {"longanswers": [(2, 64), (4, 8), (8, 512)],
         "reasoning": [(2, 24), (8, 192)],
         "agents": [(22, 512)], "mixedlengths": [(4, 256)],
         "codebases": [(8, 256)], "chains": [(1, 16)]}
SHAPES = sorted({kn for calls in CALLS.values() for kn in calls})
FORMS = ("passes", "sort")
both = pytest.mark.parametrize("form", FORMS)
shapes = pytest.mark.parametrize("k,n", SHAPES,
                                 ids=[f"{k}of{n}" for k, n in SHAPES])


def same(got, want):
    """Both arrays of both pairs equal to the bit (a NaN equal to a NaN)."""
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g.view(np.int32), w.view(np.int32))


def test_the_rule_over_the_tables_shapes():
    """What ``SELECT_PASS_STAGES`` was placed to say (the sweep of PR 62):
    the passes at every call of the table but the agents cell's 22 of 512
    and a grouped router's 4 groups of 8."""
    said = {kn: select_form(*kn) for kn in SHAPES}
    assert {kn for kn, form in said.items() if form == "sort"} == {
        (22, 512), (4, 8)}


@both
@shapes
def test_k_largest_is_top_k(k, n, form):
    """Random rows: equal values, equal indices in equal order; and over
    three axes (a router's groups), where the sort form sorts rows."""
    rng = np.random.default_rng(k * 1000 + n)
    x = jnp.asarray(rng.normal(size=(37, n)), jnp.float32)
    same(k_largest(x, k, form=form), jax.lax.top_k(x, k))
    x3 = jnp.asarray(rng.normal(size=(5, 3, n)), jnp.float32)
    same(jax.jit(lambda a: k_largest(a, k, form=form))(x3),
         jax.lax.top_k(x3, k))


@both
@shapes
def test_k_largest_ties_go_to_the_lower_index(k, n, form):
    """Scores of four levels only, so that every pick is among equals:
    the lower index first, which is ``top_k``'s rule and a stable
    descending ``argsort``'s; 0.0 ranks over -0.0 as in a sort."""
    rng = np.random.default_rng(n)
    x = rng.integers(0, 4, size=(29, n)).astype(np.float32)
    x[3] = 1.0                                    # a row of one value
    x[5, ::2], x[5, 1::2] = -0.0, 0.0
    values, ids = k_largest(jnp.asarray(x), k, form=form)
    same((values, ids), jax.lax.top_k(jnp.asarray(x), k))
    rows = [r for r in range(len(x)) if r != 5]   # argsort sees 0.0 == -0.0
    assert np.array_equal(
        np.asarray(ids)[rows],
        np.argsort(-x[rows], axis=-1, kind="stable")[:, :k])
    assert np.array_equal(np.asarray(ids)[3], np.arange(k))
    assert np.array_equal(np.asarray(ids)[5], 1 + 2 * np.arange(k)
                          ) or k > n // 2


@both
@shapes
def test_a_row_of_nan_and_a_row_of_inf_keep_to_themselves(k, n, form):
    """A row of NaN, of -inf, of +inf and of the NaN whose key is the
    struck entries' own (all bits set) among finite rows: k distinct ids
    inside [0, n) in each, ``top_k``'s; every other row untouched."""
    rng = np.random.default_rng(7 * n + k)
    x = rng.normal(size=(9, n)).astype(np.float32)
    x[1], x[4], x[6] = np.nan, -np.inf, np.inf
    x[7] = np.full(n, -1, np.int32).view(np.float32)
    x[2, : n // 2] = np.nan                       # and a row half of NaN
    values, ids = k_largest(jnp.asarray(x), k, form=form)
    ids = np.asarray(ids)
    assert ids.dtype == np.int32 and ((ids >= 0) & (ids < n)).all()
    assert all(len(set(row)) == k for row in ids.tolist())
    same((values, ids), jax.lax.top_k(jnp.asarray(x), k))
    finite = [0, 3, 5, 8]
    assert np.array_equal(
        ids[finite], np.argsort(-x[finite], axis=-1, kind="stable")[:, :k])


def test_k_largest_takes_float32_and_passes_no_gradient():
    with pytest.raises(TypeError):
        k_largest(jnp.ones((2, 8), jnp.bfloat16), 2)
    for form in FORMS:
        grad = jax.grad(lambda x: k_largest(x, 2, form=form)[0].sum())(
            jnp.arange(16.0).reshape(2, 8))
        assert not np.asarray(grad).any()


# ---- route itself ----------------------------------------------------------
ROUTERS = {
    # Ling's router whole (8 of 512 in 8 groups of which 4 stay, sigmoid
    # scores, a selection bias, normalised, scaled) over a narrow model
    "grouped_sigmoid_bias": dict(
        n_routed=512, experts_per_token=8, width=16, n_group=8, topk_group=4,
        experts_held=(0, 2), selection_bias=True, routed_scale=2.5),
    # A.X-K1's (8 of 192, groups of 24)
    "grouped_sigmoid": dict(
        n_routed=192, experts_per_token=8, width=16, n_group=8, topk_group=4,
        experts_held=(0, 2), routed_scale=2.5),
    "ungrouped_softmax": dict(
        n_routed=256, experts_per_token=4, width=16, experts_held=(0, 2),
        scoring="softmax", norm_topk=False),
    "ungrouped_softmax_top1": dict(
        n_routed=16, experts_per_token=1, width=16, experts_held=(0, 2),
        scoring="softmax", norm_topk=False, selection_bias=True),
}
E = 32


def routed_op(attrs):
    op = RoutedExperts(
        Layer(OpType.ROUTED_EXPERTS, "experts", attrs=attrs),
        [ParallelTensorShape.unpartitioned((1, 8, E), DataType.FLOAT)])
    rng = np.random.default_rng(op.n_routed)
    weights = {"router": jnp.asarray(rng.normal(size=(E, op.n_routed)),
                                     jnp.float32)}
    if op.selection_bias:
        weights["bias"] = jnp.asarray(
            0.1 * rng.normal(size=op.n_routed), jnp.float32)
    x = jnp.asarray(rng.normal(size=(24, E)), jnp.float32)
    return op, weights, x


def argsort_route(op, s, bias):
    """``route``'s selection and weights in plain numpy over the scores
    ``s``: descending, the lower index first among equals."""
    def best(a, k):
        return np.argsort(-a, axis=-1, kind="stable")[..., :k]

    choice = s + bias if bias is not None else s
    if op.n_group > 1 and op.topk_group < op.n_group:
        t = len(s)
        g = choice.reshape(t, op.n_group, -1)
        top2 = np.take_along_axis(g, best(g, min(2, g.shape[-1])), -1)
        kept = best(top2.sum(-1, dtype=np.float32), op.topk_group)
        keep = np.zeros((t, op.n_group), bool)
        keep[np.arange(t)[:, None], kept] = True
        choice = np.where(keep[:, :, None], g, np.float32(-1.0)).reshape(
            t, -1)
    ids = best(choice, op.k)
    w = np.take_along_axis(s, ids, -1)
    if op.norm_topk:
        w = w / (w.sum(-1, keepdims=True, dtype=np.float32)
                 + np.float32(1e-20))
    return ids.astype(np.int32), w * np.float32(op.routed_scale)


@pytest.mark.parametrize("stages", [None, 0.0, float("inf")],
                         ids=["rule", "passes", "sort"])
@pytest.mark.parametrize("router", list(ROUTERS))
def test_route_is_the_argsort_reference(router, stages, monkeypatch):
    """``route`` as the rule cuts it, with every pick by passes and with
    every pick by the sort (the parent's program): the reference's ids in
    its order, and weights equal to the bit among the three."""
    op, weights, x = routed_op(ROUTERS[router])

    def routed():
        return jax.jit(op.route)(weights, x)[:2]

    monkeypatch.setattr(moe_ops, "SELECT_PASS_STAGES", float("inf"))
    assert select_form(op.k, op.n_routed) == "sort"
    sorted_ids, sorted_gates = routed()
    if stages is not None:
        monkeypatch.setattr(moe_ops, "SELECT_PASS_STAGES", stages)
    else:
        monkeypatch.undo()
    ids, gates = routed()
    same((ids, gates), (sorted_ids, sorted_gates))
    logits, _ = op._router_logits(weights, x, None)
    s = np.asarray(jax.nn.sigmoid(logits) if op.scoring == "sigmoid"
                   else jax.nn.softmax(logits, axis=-1))
    want_ids, want_gates = argsort_route(
        op, s, np.asarray(weights["bias"]) if op.selection_bias else None)
    assert np.array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=2e-6)


def test_a_token_of_nan_routes_inside_the_experts():
    """PR 60's row: one token's NaN scores name k distinct experts that
    exist, and the other tokens' routing is what it was."""
    op, weights, x = routed_op(ROUTERS["grouped_sigmoid_bias"])
    ids, gates, _ = op.route(weights, x)
    bad_ids, bad_gates, _ = op.route(weights, x.at[5].set(jnp.nan))
    bad_ids = np.asarray(bad_ids)
    assert len(set(bad_ids[5].tolist())) == op.k
    assert ((bad_ids >= 0) & (bad_ids < op.n_routed)).all()
    rest = np.arange(len(x)) != 5
    assert np.array_equal(bad_ids[rest], np.asarray(ids)[rest])
    assert np.array_equal(np.asarray(bad_gates)[rest], np.asarray(gates)[rest])


# ---- the word in the engine's statistics ------------------------------------
@pytest.mark.parametrize("cell", list(CALLS))
def test_an_op_says_its_routers_form(cell):
    """``op.select_form()`` is the rule at the op's k and n_routed: the
    passes for every routed configuration's shape (Ling's 8 of 512 first)
    but the agents cell's 22 of 512."""
    k, n = CALLS[cell][-1]
    op = RoutedExperts(
        Layer(OpType.ROUTED_EXPERTS, "experts", attrs=dict(
            n_routed=n, experts_per_token=k, width=16, experts_held=(0, 1))),
        [ParallelTensorShape.unpartitioned((1, 8, E), DataType.FLOAT)])
    assert op.select_form() == select_form(k, n) == (
        "sort" if cell == "agents" else "passes")


@pytest.mark.parametrize("n_routed,want", [(64, "passes"), (8, "sort")])
def test_the_engines_statistics_name_the_form(n_routed, want):
    """``expert_stats()`` of a served model: ``select_decode`` and
    ``select_prefill`` of every routed op, 2 of 64 by passes and the toy's
    own 2 of 8 by the sort."""
    from test_latent_moe import MAX_LEN, TOY, _paged_run, _program, family

    from flexflow_tpu.serving.generation import PagedDecoder

    config = dict(TOY, published=dict(TOY["published"],
                                      n_routed_experts=n_routed))
    ff, _ = _program(config)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8)
    names = family.expert_layer_names(config)
    _paged_run(dec, names, np.arange(5, dtype=np.int32) + 3, 2)
    st = dec.expert_stats()
    assert set(st) == set(names)
    for rec in st.values():
        assert rec["n_routed"] == n_routed and rec["steps"] == 2
        assert rec["select_decode"] == rec["select_prefill"] == want
