"""Seconds of ``setup_s`` outside the program's two set-up phases and
the mix's lead-in: ``setup_s`` less ``setup_model_compile_s``,
``setup_instance_build_s`` and ``lead_in_s`` where the mix has one. The
benchmark's own work (drawing and placing the weights, the reference's
forwards, the comparison), the warm-up rounds' first dispatches, import,
reaching the chip. None where the program keeps no such sums. Layer:
Load generator."""


def read(run):
    jax_facts = run["facts"]["jax"]
    if "model_compile_s" not in jax_facts:
        return None
    return (run["setup_s"] - jax_facts["model_compile_s"]
            - jax_facts["instance_build_s"]
            - float(run["mix"].get("lead_in_s", 0.0)))
