"""What the model families share: the mapping between the benchmark's
canonical weight tree (``benchmark/reference/<family>.py``) and a program's
parameter names, given the family's two tables."""
from __future__ import annotations

import jax.numpy as jnp


def leaf_ids(top: dict, layer: dict, layer_prefix: str, n_layers: int) -> dict:
    """{program parameter name: canonical leaf id ("word", "layers/q_w/3")}.
    ``top`` and ``layer`` map canonical leaf -> program name; ``{i}`` in
    ``layer_prefix`` is the layer."""
    out = {name: key for key, name in top.items()}
    for i in range(n_layers):
        for key, name in layer.items():
            out[layer_prefix.format(i=i) + name] = f"layers/{key}/{i}"
    return out


def to_program(weights: dict, ids: dict) -> dict:
    """{program parameter name: array} from the canonical tree."""
    out = {}
    for name, leaf in ids.items():
        if leaf.startswith("layers/"):
            _, key, i = leaf.split("/")
            out[name] = weights["layers"][key][int(i)]
        else:
            out[name] = weights[leaf]
    return out


def install(model, mapped: dict) -> None:
    """Overwrite every parameter of ``model`` with the benchmark's weights
    (``mapped`` = ``to_program(canonical tree)``), in the dtype the
    parameter has; a parameter without a leaf, or a leaf without a
    parameter, is an error."""
    params = dict(model.named_parameters())
    if set(params) != set(mapped):
        raise KeyError(
            f"program and canonical weights disagree: only in the program "
            f"{sorted(set(params) - set(mapped))[:4]}, only canonical "
            f"{sorted(set(mapped) - set(params))[:4]}")
    for name, p in params.items():
        p.set_value(jnp.asarray(mapped[name], p._value.dtype))
