"""Every lowered operation says whose work it is: the scope scheme of
``core/op.py`` (``op_scope``, the fixed scopes, the sub-scopes) in the
training step and the serving programs, its inverse ``parse_scope``, and
that a scope is metadata only: the programs' lowered text, which carries
no metadata, is what it was."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          MetricsType)
from flexflow_tpu.core.op import (FIXED_SCOPES, OP_GROUPS, SUB_SCOPES, Op,
                                  fixed_scope, op_scope, parse_scope,
                                  registered_ops, scope_group, sub_scope)
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.models import zoo_smoke_builders
from flexflow_tpu.serving import PagedDecoder
from flexflow_tpu.serving.kv_cache import Addresses


def _paths(lowered) -> set:
    """The ``op_name`` paths of a lowered program (its locations)."""
    return {p for p in re.findall(r'loc\("([^"]+)"',
                                  lowered.as_text(debug_info=True))
            if p.startswith("jit(")}


def _owners(paths) -> set:
    """(type, name, sub-scopes, phase) of every path under a scope."""
    return {p for p in map(parse_scope, paths) if p is not None}


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _ints(*shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _emits(op) -> bool:
    """Whether the op lowers to anything: a reshape of nothing, an
    inference-time dropout or an identity hands its input on."""
    return op.op_type not in (OpType.INPUT, OpType.NOOP, OpType.IDENTITY,
                              OpType.DROPOUT)


# ---- the grammar -------------------------------------------------------------

class _Named(Op):
    def __init__(self, op_type, name):
        self.op_type, self.name = op_type, name


def _scope_string(op) -> str:
    """What ``op_scope`` pushes on the name stack, read back from a
    lowering under it."""
    def f(x):
        with op_scope(op):
            return x + 1.0

    (path,) = [p for p in _paths(jax.jit(f).lower(1.0)) if "add" in p]
    return path


@pytest.mark.parametrize("op_type", sorted(registered_ops(),
                                           key=lambda t: t.name),
                         ids=lambda t: t.name)
def test_parse_scope_inverts_op_scope_for_every_registered_type(op_type):
    name = f"h3.mlp.{op_type.value}"
    kind, got, subs, phase = parse_scope(_scope_string(_Named(op_type, name)))
    assert (kind, got, subs, phase) == (op_type.name, name, (), "fwd")
    assert OpType[kind] is op_type
    assert scope_group(kind) in set(OP_GROUPS) | {"other"}


@pytest.mark.parametrize("name", [
    "plain", "h3.mlp.fc", "encoder/layer_0/attention", "a(b)c", "50%/(x).y",
    "fused_relu_a_tanh_b", ".leading.dot", "trailing.", "with space",
    "ff.LINEAR.imposter"])
def test_parse_scope_gives_back_names_with_dots_slashes_and_brackets(name):
    path = _scope_string(_Named(OpType.LINEAR, name))
    assert parse_scope(path)[:2] == ("LINEAR", name)
    # the scope is one component of the path whatever the name holds
    assert len(path.split("/")) == 3 and path.count("(") == 1


def test_a_backward_path_parses_as_bwd_under_its_forwards_scope():
    op = _Named(OpType.LINEAR, "h0.fc")

    def f(w, x):
        with op_scope(op):
            with sub_scope("project"):
                y = x @ w
        with fixed_scope("loss"):
            return (y ** 2).sum()

    owners = _owners(_paths(jax.jit(jax.grad(f)).lower(
        jnp.ones((4, 4)), jnp.ones((2, 4)))))
    assert ("LINEAR", "h0.fc", ("project",), "fwd") in owners
    assert ("LINEAR", "h0.fc", ("project",), "bwd") in owners
    assert ("loss", "", (), "bwd") in owners


def test_the_innermost_scope_and_pieces_behind_a_loop_are_found():
    assert parse_scope(
        "jit(_chunk_step)/ff.BLOCK_SPARSE_ATTENTION.l1.attn/while/body/"
        "select/sort") == ("BLOCK_SPARSE_ATTENTION", "l1.attn", ("select",),
                           "fwd")
    assert parse_scope(
        "jit(f)/ff.FUSED.fused_a_b/ff.RELU.a/max") == ("RELU", "a", (), "fwd")
    assert parse_scope(
        "jit(_decode_step)/ff.ROUTED_EXPERTS.e1/ff.counters/add")[:2] == (
            "counters", "")
    assert parse_scope("jit(f)/jvp(while)/body/dot_general") is None
    assert parse_scope("") is None


def test_the_vocabularies_are_closed_and_the_groups_disjoint():
    with pytest.raises(ValueError):
        fixed_scope("optimiser")
    with pytest.raises(ValueError):
        sub_scope("sparse_select")
    assert set(FIXED_SCOPES).isdisjoint(SUB_SCOPES)
    grouped = [t for types in OP_GROUPS.values() for t in types]
    assert len(grouped) == len(set(grouped))
    assert scope_group("LINEAR") == "matmul"
    assert scope_group("GATED_DELTA_NET") == "state"
    assert scope_group("LATENT_ATTENTION") == "attention"
    assert scope_group("SOFTMAX") == scope_group("loss") == "other"


# ---- the programs -------------------------------------------------------------

def _train_step(model: str):
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          search_cache="off"))
    zoo_smoke_builders()[model](ff, 2)
    ff.compile(optimizer=AdamOptimizer(alpha=1e-3),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    spec = [s for s in ff.compiled.audit_exec if s.name == "train_step"][0]
    args = list(spec.args)               # labels a token, not a row
    args[-1] = _ints(*ff.compiled.input_tensors[0].dims)
    return ff, spec.fn.lower(*args)


def _serving_model(model: str):
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()[model](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


def _decoder(model: str):
    ff = _serving_model(model)
    return ff, PagedDecoder(ff, 32, decode_slots=2, block_size=8,
                            prefill_buckets=[16])


def _sparse_decoder():
    """The zoo's selecting toy (blocks of 4), prompts in chunks of 16."""
    ff = _serving_model("sparse_hybrid")
    return ff, PagedDecoder(ff, 32, decode_slots=2, block_size=4,
                            prefill_chunk=16)


def _chunk(dec, head: bool):
    def program(*args):
        return dec._chunk_step(*args, head=head)

    return jax.jit(program, donate_argnums=(2,)).lower(
        dec._params_sds(), _ints(1, dec.prefill_chunk), _sds(dec.pool.kv),
        _addr(dec, 1), _ints(1), _ints(1))


def _addr(dec, n):
    return Addresses(_ints(n, dec.max_blocks_per_request),
                     _ints(n) if dec.pool.num_rows else None)


def _decode(dec):
    return dec._decode.lower(
        dec._params_sds(), _ints(2), _sds(dec.pool.kv), _addr(dec, 2),
        _ints(2), _sds(dec._expert_acc), _ints(2), _ints(2, dtype=jnp.bool_))


def _prefill(dec):
    return dec._prefill_fn(16, 1).lower(
        dec._params_sds(), _ints(1, 16), _sds(dec.pool.kv), _addr(dec, 1),
        _ints(1))


def _every_op_owns_something(ops, owners):
    named = {(kind, name) for kind, name, _, _ in owners}
    missing = [op for op in ops
               if _emits(op) and (op.op_type.name, op.name) not in named]
    assert not missing, missing


@pytest.mark.parametrize("model", ["gpt", "latent_moe"])
def test_the_train_step_lowers_every_op_the_loss_and_the_update_in_scope(
        model):
    ff, lowered = _train_step(model)
    owners = _owners(_paths(lowered))
    _every_op_owns_something(ff.compiled.ops, owners)
    kinds = {(kind, phase) for kind, _, _, phase in owners}
    assert {("loss", "fwd"), ("loss", "bwd"), ("optimizer", "fwd"),
            ("metrics", "fwd")} <= kinds
    # the backward of every op that has weights lands under its scope
    for op in ff.compiled.ops:
        if op.weight_specs():
            assert (op.op_type.name, "bwd") in {
                (k, ph) for k, n, _, ph in owners if n == op.name}, op
    # nothing but the argument plumbing lies outside the scheme
    stray = {p for p in _paths(lowered)
             if "/" in p and parse_scope(p) is None}
    assert not {p for p in stray if not re.search(
        r"/(convert_element_type|mul|div|add|sub|broadcast_in_dim|"
        r"stop_gradient|pjit|reduce_sum|squeeze|reshape)$", p)}, stray


@pytest.mark.parametrize("model", ["gpt", "latent_moe", "hybrid",
                                   "sparse_hybrid"])
def test_the_serving_programs_lower_every_op_in_scope(model):
    ff, dec = (_sparse_decoder() if model == "sparse_hybrid"
               else _decoder(model))
    programs = {"decode": _decode(dec)}
    if dec.prefill_chunk:
        programs.update(chunk=_chunk(dec, False), chunk_head=_chunk(dec, True))
    else:
        programs["prefill"] = _prefill(dec)
    attn = {op.name for op in dec._attn_ops}
    for name, lowered in programs.items():
        owners = _owners(_paths(lowered))
        ops = ff.compiled.ops
        if name == "chunk":              # the walk ends behind the last
            last = ops.index(dec._attn_ops[-1])     # op that keeps something
            ops = ops[:last + 1]
        _every_op_owns_something(ops, owners)
        fixed = {kind for kind, nm, _, _ in owners if nm == ""}
        if name == "decode":
            assert "sample" in fixed
            if dec._expert_ops:
                assert "counters" in fixed
        if name in ("prefill", "chunk_head"):
            assert "tail" in fixed
        assert all(phase == "fwd" for *_, phase in owners)
        # every op that keeps something for a sequence writes it and
        # reads it under the same two words, whatever its entry kind
        for op_name in attn:
            subs = {s for _, nm, ss, _ in owners if nm == op_name for s in ss}
            group = scope_group(
                [k for k, nm, _, _ in owners if nm == op_name][0])
            if group == "attention":
                assert {"project", "write", "attend"} <= subs, (
                    name, op_name, subs)
            else:           # a state is written by its rule, or behind it
                assert group == "state"
                assert "project" in subs and subs & {"rule", "chunks"}, (
                    name, op_name, subs)
                assert subs & {"write", "rule"}, (name, op_name, subs)
        assert not {s for *_, ss, _ in owners for s in ss} - set(SUB_SCOPES)


def test_a_selecting_ops_pieces_are_named_once_each_in_a_chunk():
    lowered = _chunk(_sparse_decoder()[1], False)
    # a function JAX lowers apart (a loop's body behind ``closed_call``, an
    # inner ``jit``) keeps its locations relative to its call, and XLA
    # joins the two when it inlines: read every location, whole or not
    locs = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    for old in ("sparse_select", "sparse_attend", "lightning_chunks",
                "lightning_step", "gated_delta_prefill",
                "latent_attention_decode/", "latent_attention_prefill"):
        assert not [p for p in locs if old in p], old
    sparse = [p for p in map(parse_scope, locs)
              if p and p[0] == "BLOCK_SPARSE_ATTENTION"]
    assert {"attend", "write", "project"} <= {
        s for _, _, ss, _ in sparse for s in ss}
    # no piece inside itself: each is entered once on the way to an op
    assert all(len(set(ss)) == len(ss) for _, _, ss, _ in sparse)
    picks = [p for p in locs if p.endswith("/top_k")]
    assert picks and all(p.split("/")[-2] == "select" for p in picks), picks


# ---- a scope adds no operation --------------------------------------------------

# sha256 of ``lower(...).as_text()`` (no locations, no metadata) of the toy
# GPT's three programs, recorded on the commit before the scopes (d78531a):
# a scope is entered at trace time and leaves the program as it was.
BEFORE_THE_SCOPES = {
    "train": "b910d8faaa4dc59157d5baffb29bd2f5ec466eb49c9b8bed2e2fb14878d1376f",
    "decode": "3aafb0f57e8d64295ce268b7d45e62c31463373b34e36d3e30ea9871343f04a7",
    "prefill": "1878e51f7de936c6f1c483b255b386023f2cd48c08326fa40f65e2199d075cb3",
}


@pytest.mark.parametrize("program", sorted(BEFORE_THE_SCOPES))
def test_the_lowered_text_without_metadata_is_the_parents(program):
    if program == "train":
        lowered = _train_step("gpt")[1]
    else:
        dec = _decoder("gpt")[1]
        lowered = _decode(dec) if program == "decode" else _prefill(dec)
    text = lowered.as_text()
    assert "ff." not in text             # the plain text carries no scope
    assert "ff." in lowered.as_text(debug_info=True)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == BEFORE_THE_SCOPES[program]
