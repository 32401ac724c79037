"""Operations and bytes of a ``generate`` task of the ``mla_moe`` family
(DeepSeek-V2 at one chip's share of the routed experts), from the
configuration's published sizes and the task's shapes alone.

A task of B prompts of S tokens with N new tokens passes S + N - 1 tokens
per row through the layers: the prompt in one prefill, then N - 1 decode
steps of one token. Model FLOPs are 2 x the matmul parameters a token passes
through, with attention and the expert layer counted as this chip runs them:

- latent attention: q, kv_a and o projections for every token; prefill
  expands each token's latent into keys and values (kv_b), and its causal
  products run at q/k width nope + rope and v width v (2 (qk + v) per
  head and key); decode takes each query into the latent space and the
  latent output out again (W_uk, W_uv per head), and its products run over
  the cached latent (2 (R + rope) for scores and 2 R for values per head
  and key);
- experts: the shared experts for every token; routed rows counted as
  tokens x k x held / published experts (uniform routing, the stated
  yardstick: with random weights the router is near uniform); the router;
- the head once per generated token.
"""
from __future__ import annotations


def _dims(spec):
    return dict(
        L=spec["num_hidden_layers"], d=spec["hidden_size"],
        H=spec["num_attention_heads"], R=spec["kv_lora_rank"],
        nope=spec["qk_nope_head_dim"], rope=spec["qk_rope_head_dim"],
        v=spec["v_head_dim"], F=spec["intermediate_size"],
        f=spec["moe_intermediate_size"],
        E=spec["published"]["n_routed_experts"],
        n=spec["n_routed_experts"], k=spec["num_experts_per_tok"],
        sh=spec["n_shared_experts"], K=spec["first_k_dense_replace"],
        V=spec["vocab_size"])


def routed_rows(spec, tokens: float) -> float:
    """Rows the held experts see for ``tokens`` tokens, under uniform
    routing: tokens x k x held / published experts."""
    D = _dims(spec)
    return tokens * D["k"] * D["n"] / D["E"]


def _per_token_params(spec) -> float:
    """Matmul parameters one token passes through, over all layers, head
    excluded; routed experts at their expected rows a token."""
    D = _dims(spec)
    d, H, R = D["d"], D["H"], D["R"]
    qk = D["nope"] + D["rope"]
    attn = d * H * qk + d * (R + D["rope"]) + H * D["v"] * d
    attn += H * (D["nope"] + D["v"]) * R     # kv_b: expand, or absorb
    moe = (3 * d * D["sh"] * D["f"] + d * D["E"]
           + routed_rows(spec, 1.0) * 3 * d * D["f"])
    return (D["L"] * attn + D["K"] * 3 * d * D["F"]
            + (D["L"] - D["K"]) * moe)


def task_flops(spec, batch: int, prompt_len: int, new_tokens: int) -> float:
    """Model FLOPs of one ``generate`` task on this chip's share."""
    D = _dims(spec)
    S, n = prompt_len, new_tokens - 1
    H, L = D["H"], D["L"]
    flops = 2.0 * _per_token_params(spec) * (S + n)
    flops += 2.0 * D["d"] * D["V"] * new_tokens
    qk = D["nope"] + D["rope"]
    flops += L * H * 2.0 * (qk + D["v"]) * (S * (S + 1) // 2)
    decode_keys = sum(p + 1 for p in range(S, S + n))
    flops += L * H * 2.0 * (2 * D["R"] + D["rope"]) * decode_keys
    return batch * flops


def mla_flash_work(spec, batch: int, prompt_len: int):
    """(FLOPs, bytes) of the flash kernel's causal prefill attention over
    all layers: 2 (qk + v) per head and key of the lower triangle; q and k
    at width qk and v and o at width v read or written once in bf16."""
    D = _dims(spec)
    S, H = prompt_len, D["H"]
    qk = D["nope"] + D["rope"]
    flops = D["L"] * batch * H * 2.0 * (qk + D["v"]) * (S * (S + 1) // 2)
    nbytes = D["L"] * batch * S * H * 2.0 * (2 * qk + 2 * D["v"])
    return flops, nbytes


def expert_ffn_calls(spec, rows: int, experts_read: int):
    """(FLOPs, bytes) of grouped kernel calls that ran ``rows`` routed rows
    and read the weights of ``experts_read`` held experts, as the program's
    counters give them: 6 d f per routed row (gate, up, down); each expert
    read costs its three matrices in bf16 once (the kernel reads a held
    expert's weights once a call, and an expert left empty not at all);
    the routed rows are read and written in bf16."""
    D = _dims(spec)
    d, f = D["d"], D["f"]
    return 6.0 * d * f * rows, experts_read * 3 * d * f * 2.0 + rows * d * 4.0
