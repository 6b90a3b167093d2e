"""The GPT-2 family: how a configuration file becomes the program's
``FFModel`` graph, and how the reference's weights (GPT-2's own layout,
``benchmark/reference/gpt2.py``) become the program's parameter tree.

A family is found by the ``family`` key of a configuration's file. It
names its reference (``REFERENCE``), builds the graph through the
program's own builder, and maps weights both ways; it computes nothing.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "gpt2"


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/gpt.py``."""
    from flexflow_tpu.models.gpt import GPTConfig, build_gpt

    e = int(config["n_embd"])
    inner = config.get("n_inner") or 4 * e
    if inner % e:
        raise ValueError(f"n_inner {inner} is not a multiple of n_embd {e}")
    if config.get("activation_function") != "gelu":
        raise ValueError("models/gpt.py computes the exact GELU only; the "
                         "configuration states "
                         f"{config.get('activation_function')!r}")
    build_gpt(ff, batch, seq, GPTConfig(
        vocab_size=int(config["vocab_size"]),
        max_positions=int(config["n_positions"]), hidden_size=e,
        num_heads=int(config["n_head"]), num_layers=int(config["n_layer"]),
        mlp_ratio=inner // e))


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them."""
    import jax.numpy as jnp

    e, h = int(config["n_embd"]), int(config["n_head"])
    d = e // h
    w = weights
    out = {"wte": {"weight": w["wte"]}, "wpe": {"weight": w["wpe"]},
           "ln_f": {"scale": w["ln_f.g"], "bias": w["ln_f.b"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i in range(int(config["n_layer"])):
        wq, wk, wv = jnp.split(w[f"h{i}.attn.c_attn.w"], 3, axis=1)
        bq, bk, bv = jnp.split(w[f"h{i}.attn.c_attn.b"], 3)
        out[f"block{i}_ln1"] = {"scale": w[f"h{i}.ln_1.g"],
                                "bias": w[f"h{i}.ln_1.b"]}
        out[f"block{i}_attn"] = {
            "wq": wq.reshape(e, h, d), "wk": wk.reshape(e, h, d),
            "wv": wv.reshape(e, h, d),
            "wo": w[f"h{i}.attn.c_proj.w"].reshape(h, d, e),
            "bq": bq.reshape(h, d), "bk": bk.reshape(h, d),
            "bv": bv.reshape(h, d), "bo": w[f"h{i}.attn.c_proj.b"]}
        out[f"block{i}_ln2"] = {"scale": w[f"h{i}.ln_2.g"],
                                "bias": w[f"h{i}.ln_2.b"]}
        out[f"block{i}_mlp_up"] = {"kernel": w[f"h{i}.mlp.c_fc.w"],
                                   "bias": w[f"h{i}.mlp.c_fc.b"]}
        out[f"block{i}_mlp_down"] = {"kernel": w[f"h{i}.mlp.c_proj.w"],
                                     "bias": w[f"h{i}.mlp.c_proj.b"]}
    return out


def grads_to_reference(grads: Dict[str, Dict], config: Dict,
                       names) -> Dict:
    """The program's gradient tree, for the reference weights in
    ``names`` only (the comparison samples a few leaves)."""
    import jax.numpy as jnp

    e = int(config["n_embd"])
    out = {}
    for name in names:
        if name in ("wte", "wpe"):
            out[name] = grads[name]["weight"]
        elif name == "lm_head":
            out[name] = grads["lm_head"]["kernel"]
        elif name.startswith("ln_f."):
            out[name] = grads["ln_f"]["scale" if name.endswith(".g")
                                      else "bias"]
        else:
            blk, rest = name.split(".", 1)
            i = int(blk[1:])
            if rest == "attn.c_attn.w":
                a = grads[f"block{i}_attn"]
                out[name] = jnp.concatenate(
                    [a[k].reshape(e, e) for k in ("wq", "wk", "wv")], axis=1)
            elif rest == "attn.c_attn.b":
                a = grads[f"block{i}_attn"]
                out[name] = jnp.concatenate(
                    [a[k].reshape(e) for k in ("bq", "bk", "bv")])
            elif rest == "attn.c_proj.w":
                out[name] = grads[f"block{i}_attn"]["wo"].reshape(e, e)
            elif rest == "attn.c_proj.b":
                out[name] = grads[f"block{i}_attn"]["bo"]
            elif rest.startswith("ln_"):
                op = f"block{i}_ln{rest[3]}"
                out[name] = grads[op]["scale" if rest.endswith(".g")
                                      else "bias"]
            elif rest.startswith("mlp."):
                op = f"block{i}_mlp_" + ("up" if "c_fc" in rest else "down")
                out[name] = grads[op]["kernel" if rest.endswith(".w")
                                      else "bias"]
            else:
                raise KeyError(name)
    return out


def grad_sample_names(config: Dict):
    """The leaves the fit comparison samples: the embeddings and the
    head, and every kind of block weight at the first, a middle and the
    last block."""
    n = int(config["n_layer"])
    names = ["wte", "wpe", "lm_head", "ln_f.g", "ln_f.b"]
    for i in sorted({0, n // 2, n - 1}):
        names += [f"h{i}.{r}" for r in (
            "ln_1.g", "attn.c_attn.w", "attn.c_attn.b", "attn.c_proj.w",
            "ln_2.b", "mlp.c_fc.w", "mlp.c_fc.b", "mlp.c_proj.w")]
    return names


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``). A reader of a
# quantity that several families share takes from here what differs between
# them: which ``counts*.py`` the shapes are counted by, and which of the
# window's counters feed it. A function answers None where the window holds
# no such counters; a family that has no such quantity leaves the function
# out, and the reader then reports nothing.


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: ``counts.decode_bytes_per_step`` over the
    HBM peak. The live tokens are counted low, so that the share is a
    true lower bound: each active slot is credited its request's prompt
    alone (the mean prompt of the jobs sent), not the answer tokens it
    has cached by then; active slots are the window's
    ``slot_occupancy``."""
    from benchmark import counts, serving

    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1 or not f.get("prompt_lens"):
        return None
    active = serving.decode_tokens_per_step(s0, s1)
    if active is None:
        return None
    prompt = sum(f["prompt_lens"]) / len(f["prompt_lens"])
    return (counts.decode_bytes_per_step(run["config"], active * prompt)
            / run["peaks"]["hbm_bytes_per_s"])
