"""What a delta-rule layer's decode step pays for its convolution tails
on this chip, by form (chip only; about three minutes). A request keeps
the last ``taps - 1`` inputs of the layer's convolution as one flat row
``(tail * channels,)`` of a per-request arena; a step convolves each
slot's row with the slot's new inputs and puts the shifted row back. The
forms, each alone on donated arenas at the two cells' shapes, 6 and 12
arenas a call as a model's layers are, no slot and two slots idle, by the
host's clock over fifty calls and by the device's busy time in a profile
of one (the host takes longer to dispatch six short layers than the chip
to run them, so the wall floors near 0.2 ms a layer):

* ``i``   the lines ``StateEntry.step`` held up to PR 58: a gather of
  the slots' rows, a reshape to ``(n, tail, channels)``, the window
  ``(n, taps, channels)``, ``convolve``, ``spread_rows`` on the way back;
* ``ii``  the same in slot order with the taps left on the lanes: tap j
  of a flat row is the lanes ``[j C, (j + 1) C)``;
* ``iii`` lanes-flat in ARENA order: every arena row takes the inputs of
  the slot that names it (a gather of ``C``-wide rows), one elementwise
  pass yields the convolved rows and the shifted arena, and the slots
  gather their convolved rows back;
* ``iv``  ``iii``'s pass as one Pallas kernel over blocks of one sublane
  tile of arena rows, the arena aliased in and out
  (``kernels/gated_delta.py`` ``tails_step``: what ``StateEntry.step``
  runs since PR 59; blocks of 32 rows and runs of 1,024 lanes read within
  5 % of it).

Every form ends in the op's ``heads`` (SiLU is inside the form, the two
L2 norms behind it), as the step's ``conv`` scope does. PERF.md section 6
(PR 59) holds the table this printed.

    python tools/state_tails_forms.py [--iters 50] [--forms i,ii,iii,iv]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ops.gated_delta import unit_heads
from flexflow_tpu.ops.rows import named_by, spread_rows
from flexflow_tpu.serving.cache_entry import ConvTail

# (arena rows, slots, heads, key_dim, value_dim): channels = 2 H d_k + H d_v
SHAPES = {"ling-3.0-flash-ep8": (257, 256, 32, 128, 128),
          "olmo-hybrid-pp2": (33, 32, 30, 96, 192)}
TAPS = 4
F32 = jnp.float32


def silu_sum(w, taps):
    """``convolve``'s arithmetic: the taps' float32 products summed from
    tap 0, then SiLU."""
    w = w.astype(F32)
    return jax.nn.silu(sum(w[j] * t.astype(F32) for j, t in enumerate(taps)))


def form_i(tails, rows, x, w):
    n, c = x.shape
    window = jnp.concatenate(
        [tails[rows].reshape(n, TAPS - 1, c), x[:, None]], axis=1)
    u = silu_sum(w[:, None, None], [window[:, j:j + 1] for j in range(TAPS)])
    return u[:, 0], spread_rows(tails, rows, window[:, 1:].reshape(n, -1))


def form_ii(tails, rows, x, w):
    c = x.shape[1]
    t = tails[rows]
    u = silu_sum(w[:, None], [t[:, j * c:(j + 1) * c]
                              for j in range(TAPS - 1)] + [x])
    return u, spread_rows(tails, rows, jnp.concatenate([t[:, c:], x], -1))


def form_iii(tails, rows, x, w):
    c = x.shape[1]
    slot_of, live = named_by(tails.shape[0], rows)
    x_r = jnp.where(live[:, None], x[slot_of], 0)    # nobody's row: zeros
    u_r = silu_sum(w[:, None], [tails[:, j * c:(j + 1) * c]
                                for j in range(TAPS - 1)] + [x_r])
    tails = jnp.where(live[:, None],
                      jnp.concatenate([tails[:, c:], x_r], -1), tails)
    return u_r[rows], tails


def form_iv(tails, rows, x, w):
    u, tails = ConvTail.step_arena(tails, rows, x, w)
    return u[:, 0], tails


FORMS = {"i": form_i, "ii": form_ii, "iii": form_iii, "iv": form_iv}


def heads(u, h, dk):
    """The op's ``heads``: the convolved (n, channels) cut into q, k, v a
    head, q and k unit."""
    n = u.shape[0]
    q, k, v = (u[:, :h * dk].reshape(n, 1, h, dk),
               u[:, h * dk:2 * h * dk].reshape(n, 1, h, dk),
               u[:, 2 * h * dk:].reshape(n, 1, h, -1))
    return unit_heads(q, k, v)


def inputs(shape, idle, layers, seed=0):
    """``idle`` of the slots name row 0, the others a row each in no
    order. Returns (the arenas, the slots' rows, a layer's inputs each, the
    weights)."""
    r, slots, h, dk, dv = shape
    c = 2 * h * dk + h * dv
    rng = np.random.default_rng(seed)
    named = rng.permutation(np.arange(1, r)).astype(np.int32)[:slots]
    named[rng.permutation(slots)[:idle]] = 0
    bf = jnp.bfloat16
    arena = jnp.asarray(rng.normal(size=(r, (TAPS - 1) * c)), bf)
    xs = [jnp.asarray(rng.normal(size=(slots, c)), bf) for _ in range(layers)]
    return ([arena + i for i in range(layers)], jnp.asarray(named), xs,
            jnp.asarray(rng.normal(size=(TAPS, c)), bf))


def device_ms(fn, *args):
    """The device's busy time over one call, from a profile of it: the
    union of the ``XLA Ops`` of the TPU's plane (a call of six short
    layers takes the host longer to dispatch than the chip to run, so
    the wall says little about the fast forms)."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            out = jax.block_until_ready(fn(*args))
        found = sorted(glob.glob(os.path.join(
            tmp, "plugins", "profile", "*", "*.xplane.pb")))
        spans = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(found[-1]).planes
            if plane.name.startswith("/device:TPU")
            for line in plane.lines if line.name == "XLA Ops"
            for ev in line.events)
    busy, end = 0, 0
    for lo, hi in spans:
        busy += max(hi, end) - max(lo, end)
        end = max(hi, end)
    return out, busy / 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--forms", default=",".join(FORMS))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"chip only: the backend is {jax.default_backend()}")
    table = []
    for cell, shape in SHAPES.items():
        r, slots, h, dk, dv = shape
        c = 2 * h * dk + h * dv
        # the live rows once in and once out, the inputs in, the
        # convolved rows out
        least = slots * c * (2 * 2 * (TAPS - 1) + 2 + 4)
        for layers in (6, 12):
            for idle in (0, 2):
                want = None
                for name in args.forms.split(","):
                    form = FORMS[name]
                    arenas, named, xs, w = inputs(shape, idle, layers)
                    keep = np.asarray(named) != 0
                    before = np.asarray(arenas[0].astype(F32))

                    def step(arenas, named, xs, w, form=form):
                        out = [form(a, named, x, w)
                               for a, x in zip(arenas, xs)]
                        return ([heads(u, h, dk) for u, _ in out],
                                [a for _, a in out])

                    fn = jax.jit(step, donate_argnums=(0,))
                    try:
                        qkv, arenas = fn(arenas, named, xs, w)
                    except Exception as e:        # a form the chip refuses
                        print(json.dumps(dict(cell=cell, form=name,
                                              error=repr(e)[:300])),
                              flush=True)
                        continue
                    got = (np.concatenate([np.asarray(a).reshape(slots, -1)
                                           for a in qkv[0]], -1),
                           np.asarray(arenas[0].astype(F32)))
                    want = got if want is None else want
                    live_rows = np.asarray(named)[keep]
                    err = float(np.abs(got[0] - want[0])[keep].max())
                    same = bool((got[1][live_rows]
                                 == want[1][live_rows]).all())
                    rest = np.setdiff1d(np.arange(1, r), live_rows)
                    kept = bool((got[1][rest] == before[rest]).all())
                    for _ in range(3):
                        qkv, arenas = fn(arenas, named, xs, w)
                    jax.block_until_ready(arenas)
                    t0 = time.perf_counter()
                    for _ in range(args.iters):
                        qkv, arenas = fn(arenas, named, xs, w)
                    jax.block_until_ready((qkv, arenas))
                    ms = (time.perf_counter() - t0) / args.iters / layers * 1e3
                    (qkv, arenas), dev = device_ms(fn, arenas, named, xs, w)
                    line = dict(cell=cell, slots=slots, idle=idle,
                                layers=layers, form=name,
                                wall_ms_a_layer=round(ms, 4),
                                device_ms_a_layer=round(dev / layers, 4),
                                GBps_of_least_bytes=round(
                                    least * layers / dev / 1e6, 1),
                                qkv_err=err, live_tails_same=same,
                                free_rows_kept=kept)
                    print(json.dumps(line), flush=True)
                    table.append(line)
                    del arenas
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/state_tails_forms.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
