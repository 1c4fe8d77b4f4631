"""Model FLOPs of the granite-4.0-h backbone (GraniteMoeHybrid), counted
from its published shapes, on this chip's share of each layer.

A multiply-add is 2 FLOPs. Only the products count: norms, the conv,
SiLU, softmax and the pool are not model FLOPs. ``cfg`` is the
configuration file's dict (published key names). Per text of ``n``
tokens:

* Mamba-2 layer: the input projection (to z, x, B, C and dt), the SSD in
  its recurrent form (per token and head, the state update
  ``dt x B^T`` and the read-out ``C . state``: 4 * head_dim * d_state),
  the output projection;
* attention layer: q, k, v and o, and causal scores and weighted sums
  (token t attends t + 1 keys);
* every layer: the router over all experts, the routed experts (a
  SwiGLU of 3 products each) at ``num_experts_per_tok`` x held / all
  experts per token, the share this chip computes in expectation, and
  the shared expert.
"""
from __future__ import annotations


def _mamba(cfg: dict, n: int) -> float:
    d, h, p, s = (cfg["hidden_size"], cfg["mamba_n_heads"],
                  cfg["mamba_d_head"], cfg["mamba_d_state"])
    d_in = h * p
    proj_out = 2 * d_in + 2 * cfg["mamba_n_groups"] * s + h
    return 2 * n * d * proj_out + 4 * n * h * p * s + 2 * n * d_in * d


def _attention(cfg: dict, n: int) -> float:
    d, q, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = d // q
    proj = 2 * n * d * (q + 2 * kv) * hd + 2 * n * q * hd * d
    pairs = n * (n + 1) // 2
    return proj + 2 * 2 * pairs * q * hd


def _ffn(cfg: dict, n: int) -> float:
    d = cfg["hidden_size"]
    total = cfg["num_local_experts_published"]
    held = cfg["num_local_experts"]
    router = 2 * n * d * total
    experts = (n * cfg["num_experts_per_tok"] * held / total
               * 3 * 2 * d * cfg["intermediate_size"])
    shared = 3 * 2 * n * d * cfg["shared_intermediate_size"]
    return router + experts + shared


def text(cfg: dict, n: int) -> float:
    """One text of ``n`` tokens through every layer."""
    mixer = {"mamba": _mamba, "attention": _attention}
    return sum(mixer[kind](cfg, n) + _ffn(cfg, n)
               for kind in cfg["layer_types"])


def expert_rows(cfg: dict, rows: int) -> float:
    """The grouped expert products handed ``rows`` (token, expert)
    pairs: three products of d x intermediate each."""
    return rows * 3 * 2 * cfg["hidden_size"] * cfg["intermediate_size"]
