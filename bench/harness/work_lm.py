"""Work of the LM train step from shapes: the model FLOPs of a step of
a DeepSeek-V3-architecture model (latent attention, a leading dense
layer, MoE layers over one device's share of the experts, the SCE
head), and the operations and HBM bytes of its held experts' grouped
matmuls. Matmuls only, as ``work.py`` counts them: a ``(m, k)·(k, n)``
product is ``2·m·k·n`` operations; no recomputation; float32 operands
at 4 bytes.

``config`` is a configuration file's dict (``config.json`` keys).
"""
from __future__ import annotations

from harness.work import F32, Work


def _attention_flops(c: dict, tokens: int) -> float:
    """One MLA layer over one sequence of ``tokens``: the projections,
    and causal scores and values (query ``t`` attends ``t + 1`` keys: on
    average ``(T + 1) / 2``)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    proj = 2 * (d * h * (nope + rope) + d * (r + rope)
                + r * h * (nope + dv) + h * dv * d)
    keys = (tokens + 1) / 2
    return tokens * (proj + 2 * h * (nope + rope) * keys
                     + 2 * h * dv * keys)


def expert_flops(c: dict, assignments: float) -> float:
    """The held experts' SwiGLU forward over ``assignments`` (token,
    slot) pairs routed to them."""
    return assignments * 2 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def lm_forward_flops(c: dict, tokens: int, routed: float) -> float:
    """The backbone's forward over one sequence of ``tokens``, with
    ``routed`` assignments to held experts over its MoE layers."""
    d = c["hidden_size"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    shared = c["moe_intermediate_size"] * c["n_shared_experts"]
    dense_ffn = 2 * 3 * d * c["intermediate_size"] * tokens
    moe_fixed = (2 * 3 * d * shared + 2 * d * c["n_routed_experts"]) * tokens
    return (c["num_hidden_layers"] * _attention_flops(c, tokens)
            + n_dense * dense_ffn + n_moe * moe_fixed
            + expert_flops(c, routed))


def lm_train_step_flops(c: dict, tokens: int, routed: float, n_b: int,
                        b_x: int, b_y: int) -> float:
    """One train step: the backbone forward and backward (3× forward)
    and the SCE head over the ``vocab_size`` rows (``work.py``'s
    counts: the Mix projection, the bucket scores against positions and
    rows, the in-bucket logits forward and backward)."""
    d = c["hidden_size"]
    return (3 * lm_forward_flops(c, tokens, routed)
            + 2 * 2 * n_b * tokens * d
            + 2 * n_b * c["vocab_size"] * d
            + 3 * 2 * n_b * b_x * b_y * d)


def held_experts(c: dict, routed: float, n_moe: int) -> Work:
    """The held experts' grouped matmuls of one step, forward and
    backward (3× the forward's operations), over ``routed`` assignments
    summed over the ``n_moe`` MoE layers. Bytes: each layer's held
    expert weights read by the forward and the backward and their
    gradient written (3 passes), and the routed rows' inputs and
    outputs at ``d`` and at the expert width, once per pass."""
    d, f, held = (c["hidden_size"], c["moe_intermediate_size"],
                  c["experts_held"])
    weights = n_moe * 3 * held * d * f * F32
    rows = routed * (2 * d + 3 * f) * F32
    return 3 * expert_flops(c, routed), 3 * (weights + rows)
