"""Operations and bytes that a served task needs, from the configuration's
published sizes and the task's shapes alone, never from the program.

Model FLOPs per token are 2 x the matmul parameters a token passes through
(the embedding lookup is not a product), plus, for attention, the causal
score and value products at the token's position, and, for Mamba2, the
state-space products. A task of B prompts of S tokens with N new tokens
passes S + N - 1 tokens per row through the layers (the prompt, then N - 1
decode steps) and evaluates the head N times per row.
"""
from __future__ import annotations


def _dense(spec):
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return (spec["num_hidden_layers"], d, H, spec["num_key_value_heads"],
            d // H, spec["intermediate_size"], spec["vocab_size"])


def _ssm(spec):
    s = spec["ssm_cfg"]
    di = s["expand"] * spec["d_model"]
    return (spec["n_layer"], spec["d_model"], di, di // s["headdim"],
            s["headdim"], s["d_state"], s["ngroups"], s["chunk_size"],
            spec["vocab_size"])


def layer_matmul_params(spec) -> int:
    """Matmul parameters of all layers together."""
    if spec["family"] == "dense":
        L, d, H, KVH, hd, F, V = _dense(spec)
        return L * (d * (H + 2 * KVH) * hd + H * hd * d + 3 * d * F)
    L, d, di, H, P, N, G, Q, V = _ssm(spec)
    return L * (d * (2 * di + 2 * G * N + H) + di * d)


def head_params(spec) -> int:
    d = spec["hidden_size"] if spec["family"] == "dense" else spec["d_model"]
    return d * spec["vocab_size"]


def _mixer_flops(spec, positions_sum: int, tokens: int, decode: bool) -> float:
    """Attention or state-space FLOPs of ``tokens`` tokens of one row, over
    all layers; ``positions_sum`` is the sum of (position + 1)."""
    if spec["family"] == "dense":
        L, d, H, KVH, hd, F, V = _dense(spec)
        return L * 4.0 * H * hd * positions_sum
    L, d, di, H, P, N, G, Q, V = _ssm(spec)
    per_token = 5 * P * N if decode else (Q + 1) * (N + P) + 4 * N * P
    return L * H * float(per_token) * tokens


def task_flops(spec, batch: int, prompt_len: int, new_tokens: int) -> float:
    """Model FLOPs of one ``generate`` task."""
    S, n = prompt_len, new_tokens - 1
    through = S + n
    flops = 2.0 * layer_matmul_params(spec) * through
    flops += 2.0 * head_params(spec) * new_tokens
    flops += _mixer_flops(spec, S * (S + 1) // 2, S, decode=False)
    decode_pos = sum(p + 1 for p in range(S, S + n))
    flops += _mixer_flops(spec, decode_pos, n, decode=True)
    return batch * flops


def flash_attention_work(spec, batch: int, prompt_len: int):
    """(FLOPs, bytes) of causal prefill attention over all layers: the score
    and value products of the lower triangle, and q, k, v read and o
    written once in bfloat16."""
    L, d, H, KVH, hd, F, V = _dense(spec)
    S = prompt_len
    flops = L * batch * 4.0 * H * hd * (S * (S + 1) // 2)
    nbytes = L * batch * S * 2.0 * (2 * H + 2 * KVH) * hd
    return flops, nbytes


def ssd_scan_work(spec, batch: int, prompt_len: int):
    """(FLOPs, bytes) of the chunked SSD scan over all layers, at the
    configuration's chunk size Q: per head and chunk, the causal C.B^T and
    (decay-weighted) product with x, the carried state's read-out and the
    state update. Bytes: x and y in bfloat16, dt in float32, B and C in
    bfloat16, the final state in float32."""
    L, d, di, H, P, N, G, Q, V = _ssm(spec)
    S = prompt_len
    flops = L * batch * H * float(S) * ((Q + 1) * (N + P) + 4 * N * P)
    nbytes = L * batch * (S * H * (2 * P * 2 + 4) + S * 2 * G * N * 2
                          + H * P * N * 4.0)
    return flops, nbytes
