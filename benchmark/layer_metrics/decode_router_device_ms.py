"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) under the sub-scope ``route`` of the
``ROUTED_EXPERTS`` ops, all layers together: the router's products, its
scores and its choice (an MLP router's chain of small float32 products
over the state that runs down the layers is the step's latency-bound
part), from the owner table of the traced window
(``benchmark/owners.py``). None where the profile holds no such scope.
Layer: Expert layer."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("ROUTED_EXPERTS",),
                            subs=("route",))
