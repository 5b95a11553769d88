"""What the blocks' dense feed-forward layers have to compute in one
training step, from shapes alone: the numerator of `mlp_roofline`. The
rules are `flops.py`'s: forward + backward = 3 x forward (each product
has two gradient products of its own size), 2 K N FLOPs a token for a
product from width K to width N, nothing recomputed, nothing
elementwise. `dims` are the model's keyword arguments as the
configuration's file gives them (`model_kwargs`, `model_options`).

A dense feed-forward is, by family:

* the GPT-2 / BERT block (`models/transformer.py::MlpBlock`; `dims`
  has `d_ff`): `wi` from `d_model` to `d_ff`, `wo` back, every layer;
* the routed families' leading dense layers
  (`models/latent_moe.py::GatedMLP`): `gate` and `up` from
  `hidden_size` to `intermediate_size`, `down` back; the layers whose
  `mlp_layer_types` entry is `dense` (`models/window_moe.py`), else the
  first `first_k_dense_replace`. A multi-token-prediction module's
  block is routed and holds none.
"""
from __future__ import annotations

from benchmark import flops

DENSE = "dense"


def dense_ffn_products(dims: dict) -> list:
    """(name, K, N, layers) of every product of the dense feed-forward
    layers one step's model holds."""
    if "d_ff" in dims:
        d, f, layers = dims["d_model"], dims["d_ff"], dims["n_layers"]
        return [("wi", d, f, layers), ("wo", f, d, layers)]
    d, f = dims["hidden_size"], dims["intermediate_size"]
    if "mlp_layer_types" in dims:
        layers = list(dims["mlp_layer_types"][:dims["num_hidden_layers"]]
                      ).count(DENSE)
    else:
        layers = dims["first_k_dense_replace"]
    return [("gate", d, f, layers), ("up", d, f, layers),
            ("down", f, d, layers)]


def product_cost(tokens: int, k: int, n: int, backward: bool,
                 itemsize: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one product over `tokens` tokens from width
    `k` to width `n`. Backward is twice the forward: a gradient for the
    input, one for the weight. Bytes that must cross HBM once in each:
    the input and output activations and the weight (a gradient's
    operands and result are the same three sizes)."""
    passes = 2 if backward else 1
    flops_ = passes * 2.0 * tokens * k * n
    return flops_, float(passes * (tokens * (k + n) + k * n) * itemsize)


def least_seconds(dims: dict, tokens: int, peaks) -> list:
    """(name, pass, bound, seconds of all its layers) of each product and
    pass: each by the larger of its FLOPs over the peak and its bytes
    over the bandwidth (`flops.least_seconds`)."""
    rows = []
    for backward in (False, True):
        for name, k, n, layers in dense_ffn_products(dims):
            seconds, bound = flops.least_seconds(
                *product_cost(tokens, k, n, backward), peaks)
            rows.append((name, "backward" if backward else "forward", bound,
                         seconds * layers))
    return rows
