"""Program audit (analysis/program_audit.py): jaxpr-level AUD0xx checks.

Three layers of coverage: seeded fixtures that deliberately commit each
auditable sin (a baked megabyte constant, a dropped donation, a host
callback, a bf16 gradient accumulator, a corrupt ppermute table,
switch branches that disagree on collectives, a weak-typed scalar
closure) — each asserting the EXACT finding code; the compile()/
pipeline/serving gate wiring; and the AUD002-driven eval-label donation
proven bit-identical with a reduced peak-live estimate. The shared
pragma grammar (analysis/pragmas.py) and the caller-side donated-reuse
lint are covered here too.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.analysis import (CODE_CATALOG, PCGValidationError,
                                   ProgramAuditError)
from flexflow_tpu.analysis import pragmas
from flexflow_tpu.analysis.findings import ValidationReport
from flexflow_tpu.analysis.program_audit import (ExecutableSpec,
                                                 audit_closed_jaxpr,
                                                 audit_spec, audit_traced,
                                                 lint_donated_reuse)
from flexflow_tpu.models import build_mlp
from jax import shard_map

BS = 32
F32 = jnp.float32


def _sds(shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile_mlp(loss=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, bs=BS,
                 num_classes=10, **cfg_kw):
    ff = FFModel(FFConfig(batch_size=bs, seed=0, **cfg_kw))
    build_mlp(ff, bs, in_dim=64, hidden_dims=(128,),
              num_classes=num_classes)
    ff.compile(optimizer=SGDOptimizer(lr=0.01), loss_type=loss,
               metrics=[])
    return ff


# ------------------------------------------------ pragma grammar (shared)
def test_pragma_parse_and_reason_required():
    ps = pragmas.parse_line(
        "x = f(y)  # audit: const-ok (4KB table)  # hotpath: sync-ok ()")
    assert (ps[0].tool, ps[0].token, ps[0].reason) == \
        ("audit", "const-ok", "4KB table")
    assert ps[0].ok()
    assert not ps[1].ok()  # empty reason does not suppress
    assert pragmas.parse_line("# audit: donate-ok")[0].reason is None


def test_pragma_line_has():
    lines = ["a = 1", "b = f(a)  # audit: callback-ok (logging step)"]
    assert pragmas.line_has(lines, 2, "audit", "callback-ok")
    assert not pragmas.line_has(lines, 2, "audit", "const-ok")
    assert not pragmas.line_has(lines, 1, "audit", "callback-ok")
    assert not pragmas.line_has(lines, 99, "audit", "callback-ok")


def test_pragma_lint_reasonless():
    src = ("x = 1  # audit: const-ok\n"
           "y = 2  # hotpath: sync-ok (measured, once per epoch)\n"
           "z = 3  # audit: accum-ok ( )\n")
    bad = pragmas.lint_reasonless(src)
    assert [(ln, p.token) for ln, p in bad] == \
        [(1, "const-ok"), (3, "accum-ok")]


def test_hotpath_lint_shares_grammar():
    """A reasonless hotpath pragma no longer suppresses: the shared
    grammar demands the review trail."""
    from flexflow_tpu.analysis import lint_hotpath_source

    tmpl = ("import numpy as np\n"
            "def fit(self):\n"
            "    for i in range(n):\n"
            "        loss = self.compiled.train_step(p, s, rng, x, y)\n"
            "        self.h.append(float(loss)){pragma}\n")
    with_reason = tmpl.format(
        pragma="  # hotpath: sync-ok (guard check, every step by design)")
    without = tmpl.format(pragma="  # hotpath: sync-ok")
    assert lint_hotpath_source(with_reason, filename="runtime/x.py") == []
    assert [f.code for f in
            lint_hotpath_source(without, filename="runtime/x.py")] == \
        ["HOT001"]


# --------------------------------------------------- AUD fixture tests
def test_aud001_large_const_baked():
    big = jnp.asarray(np.ones((512, 1024), np.float32))  # 2 MiB
    fn = jax.jit(lambda x: x @ big)
    report = audit_traced("fix1", fn.trace(_sds((4, 512))))
    assert "AUD001" in report.codes()
    [f] = [f for f in report.findings if f.code == "AUD001"]
    assert f.severity == "warning" and "2.0MiB" in f.message


def test_aud001_pragma_suppresses():
    big = jnp.asarray(np.ones((512, 1024), np.float32))
    fn = jax.jit(lambda x: x @ big)  # audit: const-ok (seeded fixture)
    report = audit_traced("fix1s", fn.trace(_sds((4, 512))))
    assert "AUD001" not in report.codes()
    assert report.programs["fix1s"]["suppressed"] == 1


def test_aud002_missing_donation():
    fn = jax.jit(lambda x: x * 2)  # output aval == input aval, 2 MiB
    report = audit_traced("fix2", fn.trace(_sds((512, 1024))))
    assert report.codes() == ["AUD002"]
    fn_d = jax.jit(lambda x: x * 2, donate_argnums=(0,))
    assert audit_traced("fix2d", fn_d.trace(_sds((512, 1024)))).ok()
    assert audit_traced(
        "fix2d", fn_d.trace(_sds((512, 1024)))).findings == []


def test_aud002_small_args_ignored():
    fn = jax.jit(lambda x: x * 2)  # matching aval but < threshold
    assert audit_traced("fix2s", fn.trace(_sds((8, 8)))).findings == []


def test_aud003_host_callback():
    def step(x):
        jax.debug.print("loss={l}", l=x.sum())
        return x * 1.5

    report = audit_traced("fix3", jax.jit(step).trace(_sds((8,))))
    assert [f.code for f in report.errors] == ["AUD003"]
    assert "debug" in report.errors[0].message


def test_aud004_bf16_accumulator():
    def accum(xs):
        def body(c, x):
            return c + x.astype(jnp.bfloat16), ()

        c, _ = jax.lax.scan(body, jnp.zeros((8,), jnp.bfloat16), xs)
        return c

    report = audit_traced("fix4", jax.jit(accum).trace(_sds((16, 8))))
    assert [f.code for f in report.errors] == ["AUD004"]
    assert "bfloat16" in report.errors[0].message

    def accum32(xs):  # the fix: accumulate in f32
        def body(c, x):
            return c + x, ()

        c, _ = jax.lax.scan(body, jnp.zeros((8,), jnp.float32), xs)
        return c.astype(jnp.bfloat16)

    assert audit_traced(
        "fix4ok", jax.jit(accum32).trace(_sds((16, 8)))).findings == []


def _pipe_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("p",))


def test_aud005_corrupt_ppermute_table():
    mesh = _pipe_mesh()

    def bad(x):  # rank 1 receives twice, rank 2 never
        return jax.lax.ppermute(x, "p", [(0, 1), (1, 1), (2, 3), (3, 0)])

    fn = jax.jit(shard_map(bad, mesh=mesh, in_specs=PartitionSpec("p"),
                           out_specs=PartitionSpec("p")))
    report = audit_traced("fix5", fn.trace(_sds((8, 4))))
    assert [f.code for f in report.errors] == ["AUD005"]
    assert "duplicate destination" in report.errors[0].message


def test_aud005_out_of_range_rank():
    mesh = _pipe_mesh()

    def bad(x):
        return jax.lax.ppermute(x, "p", [(0, 1), (1, 7)])

    fn = jax.jit(shard_map(bad, mesh=mesh, in_specs=PartitionSpec("p"),
                           out_specs=PartitionSpec("p")))
    report = audit_traced("fix5r", fn.trace(_sds((8, 4))))
    assert [f.code for f in report.errors] == ["AUD005"]
    assert "out of range" in report.errors[0].message


def test_aud005_branch_collective_divergence():
    mesh = _pipe_mesh()
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def branchy(x, s):
        return jax.lax.switch(
            s, (lambda v: jax.lax.psum(v, "p"),
                lambda v: jax.lax.ppermute(v, "p", ring)), x)

    fn = jax.jit(shard_map(
        partial(branchy), mesh=mesh,
        in_specs=(PartitionSpec("p"), PartitionSpec()),
        out_specs=PartitionSpec("p"), check_vma=False))
    report = audit_traced(
        "fix5b", fn.trace(_sds((8, 4)), _sds((), jnp.int32)))
    assert [f.code for f in report.errors] == ["AUD005"]
    assert "disagree" in report.errors[0].message

    def agree(x, s):  # same collective sequence in both branches: legal
        return jax.lax.switch(
            s, (lambda v: jax.lax.psum(v * 2, "p"),
                lambda v: jax.lax.psum(v + 1, "p")), x)

    fn_ok = jax.jit(shard_map(
        partial(agree), mesh=mesh,
        in_specs=(PartitionSpec("p"), PartitionSpec()),
        out_specs=PartitionSpec("p"), check_vma=False))
    assert audit_traced(
        "fix5ok", fn_ok.trace(_sds((8, 4)), _sds((), jnp.int32))).ok()


def test_aud006_weak_scalar_closure():
    lr = jnp.asarray(0.125)  # weak-typed device scalar closure
    assert lr.weak_type
    fn = jax.jit(lambda x: x * lr)
    report = audit_traced("fix6", fn.trace(_sds((4,))))
    assert report.codes() == ["AUD006"]
    assert report.findings[0].severity == "warning"
    assert "0.125" in report.findings[0].message


def test_aud006_unhashable_static():
    closed = jax.jit(lambda x: x * 2).trace(_sds((4,))).jaxpr
    report = audit_closed_jaxpr("fix6u", closed,
                                static_args={"shapes": [1, 2]})
    assert [f.code for f in report.errors] == ["AUD006"]
    assert "unhashable" in report.errors[0].message


def test_aud000_trace_failure_is_warning():
    def boom(x):
        raise ValueError("fixture refuses to trace")

    report = audit_spec(ExecutableSpec("broken", jax.jit(boom),
                                       (_sds((4,)),)))
    assert [(f.code, f.severity) for f in report.findings] == \
        [("AUD000", "warning")]
    assert report.programs["broken"]["trace_failed"]
    assert "AUD000" in CODE_CATALOG


# ------------------------------------ AUD002 caller-side: donated reuse
_REUSE_SRC = """
def run(cm, params, state, rng, x, y):
    loss = cm.train_step(params, state, rng, x, y)
    return loss, params["w"]{pragma}
"""


def test_donated_reuse_flags_read_after_donation():
    findings = lint_donated_reuse(_REUSE_SRC.format(pragma=""))
    assert [f.code for f in findings] == ["AUD002"]
    assert findings[0].severity == "error"
    assert "'params'" in findings[0].message


def test_donated_reuse_pragma_suppresses():
    src = _REUSE_SRC.format(
        pragma="  # audit: donate-ok (host copy taken before the call)")
    assert lint_donated_reuse(src) == []


def test_donated_reuse_rebind_is_safe():
    src = ("def run(cm, params, state, rng, x, y):\n"
           "    params, state, loss = cm.train_step(params, state, rng,"
           " x, y)\n"
           "    return loss, params\n")
    assert lint_donated_reuse(src) == []


def test_donated_reuse_eval_label_last_positional():
    # eval_step donates its LAST positional (the label, after a
    # model-dependent number of inputs)
    src = ("def run(cm, p, x1, x2, y):\n"
           "    loss, logits, bm = cm.eval_step(p, x1, x2, y)\n"
           "    return y.mean()\n")
    f = lint_donated_reuse(src)
    assert [x.code for x in f] == ["AUD002"] and "'y'" in f[0].message


def test_donated_reuse_scoped_to_same_function():
    # a nested function's own same-named parameter is a DIFFERENT
    # binding — reading it must not be flagged as reuse of the outer
    # donated buffer
    src = ("def run(cm, params, state, rng, x, y):\n"
           "    out = cm.train_step(params, state, rng, x, y)\n"
           "    def report(params):\n"
           "        return params.keys()\n"
           "    f = lambda params: params\n"
           "    return out, report, f\n")
    assert lint_donated_reuse(src) == []


def test_donated_reuse_arity_and_call_form_guards():
    # the 3-positional pipelined train_step donates nothing; bare-name
    # calls are the raw (non-donating) step functions
    src = ("def a(pm, rng, xs, y):\n"
           "    loss = pm.train_step(rng, xs, y)\n"
           "    return loss, rng\n"
           "def b(params, state, rng, x, y):\n"
           "    loss = train_step(params, state, rng, x, y)\n"
           "    return loss, params\n")
    assert lint_donated_reuse(src) == []


# ------------------------------------------------------- compile() gate
def test_compile_gate_publishes_audit_report():
    from flexflow_tpu.obs.metrics import metrics_registry

    before = metrics_registry().counter("audit.programs").value
    ff = _compile_mlp()
    report = ff.audit_report
    assert report is not None and report.ok(), report.format()
    assert set(report.programs) == {"train_step", "eval_step"}
    for stats in report.programs.values():
        assert stats["eqns"] > 0
        assert stats["walk_s"] >= 0 and stats["trace_s"] >= 0
    prof = ff.audit_profile
    assert prof["wall_time_s"] > 0
    assert prof["walk_s"] <= prof["wall_time_s"]
    assert metrics_registry().counter("audit.programs").value >= before + 2


def test_compile_gate_off():
    ff = _compile_mlp(audit_programs="off")
    assert ff.audit_report is None and ff.audit_profile is None


def test_compile_gate_typo_mode_rejected():
    ff = FFModel(FFConfig(batch_size=BS, audit_programs="errorr"))
    build_mlp(ff, BS, in_dim=64, hidden_dims=(128,), num_classes=10)
    with pytest.raises(ValueError, match="audit_programs"):
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)


def test_program_audit_error_class():
    report = ValidationReport(source="fixture", tag="audit")
    report.add("AUD003", "host callback in step", severity="error")
    with pytest.raises(ProgramAuditError, match="AUD003"):
        report.handle("error")
    # subclasses PCGValidationError: existing except-clauses keep working
    assert issubclass(ProgramAuditError, PCGValidationError)
    printed = []
    report.handle("warn", printer=lambda s, **k: printed.append(s))
    assert printed and printed[0].startswith("[audit]")


# ------------------------------- AUD002-driven eval-label donation
def test_eval_label_donated_for_dense_loss_only():
    dense = _compile_mlp(LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)
    sparse = _compile_mlp(LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    assert dense.audit_report.programs["eval_step"]["donated_args"] == 1
    assert sparse.audit_report.programs["eval_step"]["donated_args"] == 0
    # and both audit clean — the sparse label has no matching output
    # aval, so its non-donation is not an AUD002 either
    assert dense.audit_report.ok() and not dense.audit_report.findings
    assert sparse.audit_report.ok() and not sparse.audit_report.findings


def test_eval_label_donation_bit_identical():
    """Donation aliases buffers; it must never change values. The
    donated eval executable's outputs equal a re-jitted UNDONATED copy
    of the same function, bit for bit."""
    ff = _compile_mlp()
    cm = ff.compiled
    [spec] = [s for s in cm.audit_exec if s.name == "eval_step"]
    rng = np.random.default_rng(0)
    x = rng.normal(size=(BS, 64)).astype(np.float32)
    y = rng.normal(size=(BS, 10)).astype(np.float32)
    undonated = jax.jit(spec.fn.__wrapped__, static_argnums=0)
    ref = undonated(-1, cm.params, jnp.asarray(x), jnp.asarray(y))
    got = spec.fn(-1, cm.params, jnp.asarray(x), jnp.asarray(y))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_eval_label_donation_reduces_peak_live():
    """The motivation's 'silently un-donated buffer doubles peak HBM':
    with a logits-dominated model, the audit's static liveness estimate
    shows the donated eval step holding strictly less than the
    undonated build of the same program."""
    ff = _compile_mlp(num_classes=4096, bs=64)  # logits/label: 1 MiB
    cm = ff.compiled
    [spec] = [s for s in cm.audit_exec if s.name == "eval_step"]
    don = audit_traced("don", spec.fn.trace(*spec.args))
    undon = audit_traced(
        "undon",
        jax.jit(spec.fn.__wrapped__, static_argnums=0).trace(*spec.args))
    dstat = don.programs["don"]
    ustat = undon.programs["undon"]
    assert dstat["donated_args"] == 1 and ustat["donated_args"] == 0
    assert dstat["peak_live_bytes"] < ustat["peak_live_bytes"]
    assert dstat["peak_live_buffers"] <= ustat["peak_live_buffers"]
    # the undonated build is exactly what AUD002 exists to flag
    assert "AUD002" in undon.codes()


def test_train_step_donation_audits_clean():
    """The historical train-step donation (params, opt_state) satisfies
    the coverage check — the gate would have flagged a regression."""
    ff = _compile_mlp()
    stats = ff.audit_report.programs["train_step"]
    assert stats["donated_args"] >= 2
    assert "AUD002" not in ff.audit_report.codes()


# ----------------------------------------- pipeline + serving wiring
def test_pipeline_compiled_engine_audited():
    from flexflow_tpu import make_mesh
    from flexflow_tpu.parallel.pipeline import PipelineConfig

    bs = 16
    ff = FFModel(FFConfig(batch_size=bs, seed=0))
    t = ff.create_tensor((bs, 32), name="input")
    for i in range(4):
        t = ff.dense(t, 32, name=f"fc{i}")
    t = ff.softmax(ff.dense(t, 8, name="head"))
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[],
               mesh=make_mesh({"pipe": 2}, devices=jax.devices()[:2]),
               pipeline=PipelineConfig(num_stages=2, num_microbatches=4,
                                       schedule="1f1b"))
    pm = ff.pipelined
    assert pm.engine_name == "compiled"
    assert pm.audit_report is None  # programs build lazily, on shapes
    rng = np.random.default_rng(3)
    x = rng.normal(size=(bs, 32)).astype(np.float32)
    y = rng.integers(0, 8, size=(bs, 1)).astype(np.int32)
    pm.train_step(jax.random.key(0), [jnp.asarray(x)], jnp.asarray(y))
    report = pm.audit_report
    assert report is not None and report.ok(), report.format()
    [stats] = report.programs.values()
    assert stats["eqns"] > 0


def test_serving_decode_step_audited():
    from flexflow_tpu.models import GPTConfig, build_gpt
    from flexflow_tpu.serving import Generator

    ff = FFModel(FFConfig(batch_size=2, seed=0))
    build_gpt(ff, 2, 8, GPTConfig(vocab_size=64, max_positions=32,
                                  hidden_size=32, num_heads=4,
                                  num_layers=2))
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[])
    gen = Generator(ff, max_length=16)
    report = gen.audit_report
    assert report is not None and report.ok(), report.format()
    assert "serving.decode_step" in report.programs
    # the KV cache rides donate_argnums=(2,): coverage shows it
    assert report.programs["serving.decode_step"]["donated_args"] > 0


# ------------------------------------------- gate ordering (PCG first)
def test_pcg016_nonpositive_dims_caught_before_lowering():
    from flexflow_tpu.ffconst import DataType, PoolType

    ff = FFModel(FFConfig(batch_size=4))
    x = ff.create_tensor((4, 8, 2, 2), DataType.FLOAT, name="in")
    t = ff.pool2d(x, 7, 7, 1, 1, 0, 0, PoolType.AVG)  # window > input
    t = ff.flat(t)
    ff.dense(t, 10)
    with pytest.raises(PCGValidationError, match="PCG016"):
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)


def test_warn_mode_lowering_failure_prints_coded_finding(capsys):
    """validate_pcg=warn proceeds past an error finding by contract —
    but when lowering then dies, the user must see the CODED finding
    that predicted it next to the raw error (satellite: gate ordering).
    The original exception type is preserved: the failure may be
    unrelated (OOM, a user-callback bug) and callers catch specific
    types, so the coded findings arrive as printed context, not as a
    rewritten exception."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.tensor import Tensor
    from flexflow_tpu.ffconst import DataType, OpType

    ff = FFModel(FFConfig(batch_size=BS, validate_pcg="warn"))
    build_mlp(ff, BS, in_dim=64, hidden_dims=(128,), num_classes=10)
    t_in = ff.layers[-1].outputs[0]
    bogus = Layer(OpType.FUSED_PARALLEL, name="bogus", inputs=[t_in])
    bogus.outputs.append(Tensor((BS, 10), DataType.FLOAT,
                                owner_layer=bogus, name="bogus:out0"))
    ff.layers.append(bogus)
    with pytest.raises(Exception) as ei:
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)
    assert not isinstance(ei.value, PCGValidationError)  # type preserved
    assert "PCG012" in capsys.readouterr().err  # coded finding printed


# ----------------------------------------------------------- zoo tool
def test_tool_subset_clean(capsys, tmp_path):
    from tools.program_audit import main

    out_file = tmp_path / "audit.json"
    rc = main(["--model", "mlp,transformer", "--out", str(out_file)])
    assert rc == 0
    import json

    line = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc["exit"] == 0
    assert set(doc["models"]) == {"mlp", "transformer"}
    for rec in doc["models"].values():
        assert rec["errors"] == 0 and rec["warnings"] == 0
        assert rec["audit_frac"] < 0.05  # the <5%-of-compile budget
        assert {"train_step", "eval_step"} <= set(rec["programs"])
    assert doc["donated_reuse"]["errors"] == 0
    assert "AUD005" in doc["codes"]
    assert out_file.read_text().strip() == line
