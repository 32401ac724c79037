"""Operations and bytes of a ``generate`` task of the ``hybrid_moe`` family
(IBM Granite 4.0-H at one chip's share of the routed experts), from the
configuration's published sizes and the task's shapes alone.

A task of B prompts of S tokens with N new tokens passes S + N - 1 tokens
per row through the layers: the prompt in one prefill, then N - 1 decode
steps of one token. Model FLOPs are 2 x the matmul parameters a token passes
through, plus each mixer's own products:

- Mamba2 layers: in_proj and out_proj for every token; the chunked SSD at
  the configuration's chunk size Q in prefill, per head and token the
  causal C.B^T and its decay-weighted product with x within the chunk, the
  carried state's read-out and the state update, (Q + 1)(N + P) + 4 N P;
  in decode 5 P N per head and token (state update and read-out);
- attention layers: q, k, v and o projections for every token (GQA: K/V
  at the K/V heads' width), and the causal score and value products of
  every query head, 4 H hd per key;
- expert layers, after every mixer: the router, the shared SwiGLU for every
  token, and routed rows counted as tokens x k x held / published experts
  (uniform routing, the stated yardstick: with random weights the router is
  near uniform);
- the tied head once per generated token.
"""
from __future__ import annotations


def _dims(spec):
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    types = spec["layer_types"]
    return dict(
        d=d, H=H, KVH=spec["num_key_value_heads"], hd=d // H,
        V=spec["vocab_size"], di=spec["mamba_expand"] * d,
        mh=spec["mamba_n_heads"], P=spec["mamba_d_head"],
        N=spec["mamba_d_state"], G=spec["mamba_n_groups"],
        W=spec["mamba_d_conv"], Q=spec["mamba_chunk_size"],
        f=spec["intermediate_size"], fs=spec["shared_intermediate_size"],
        E=spec["published"]["num_local_experts"],
        n=spec["num_local_experts"], k=spec["num_experts_per_tok"],
        Lm=types.count("mamba"), La=types.count("attention"),
        L=len(types))


def routed_rows(spec, tokens: float) -> float:
    """Rows the held experts see for ``tokens`` tokens, under uniform
    routing: tokens x k x held / published experts."""
    D = _dims(spec)
    return tokens * D["k"] * D["n"] / D["E"]


def _mamba_params(D) -> int:
    return D["d"] * (2 * D["di"] + 2 * D["G"] * D["N"] + D["mh"]) \
        + D["di"] * D["d"]


def _attn_params(D) -> int:
    return D["d"] * (D["H"] + 2 * D["KVH"]) * D["hd"] + D["H"] * D["hd"] \
        * D["d"]


def params(spec) -> int:
    """This chip's parameters: embedding (tied), the mixers with their
    norms, conv and scan parameters, the expert layers with the held
    experts, the final norm."""
    D = _dims(spec)
    d, di, mh = D["d"], D["di"], D["mh"]
    conv = di + 2 * D["G"] * D["N"]
    mamba = _mamba_params(D) + D["W"] * conv + conv + 3 * mh + di + d
    attn = _attn_params(D) + d
    moe = d + d * D["E"] + 3 * d * D["fs"] + D["n"] * 3 * d * D["f"]
    return (D["V"] * d + d + D["Lm"] * mamba + D["La"] * attn
            + D["L"] * moe)


def kv_bytes_per_token(spec) -> int:
    """K and V of every attention layer, bf16."""
    D = _dims(spec)
    return D["La"] * 2 * D["KVH"] * D["hd"] * 2


def state_bytes_per_sequence(spec) -> int:
    """The Mamba2 layers' float32 conv window (W - 1 inputs of the conv's
    width) and scan state (heads x head width x state)."""
    D = _dims(spec)
    conv = D["di"] + 2 * D["G"] * D["N"]
    return D["Lm"] * 4 * ((D["W"] - 1) * conv + D["mh"] * D["P"] * D["N"])


def _per_token_params(spec) -> float:
    """Matmul parameters one token passes through, over all layers, head
    excluded; routed experts at their expected rows a token."""
    D = _dims(spec)
    d = D["d"]
    moe = (d * D["E"] + 3 * d * D["fs"]
           + routed_rows(spec, 1.0) * 3 * d * D["f"])
    return D["Lm"] * _mamba_params(D) + D["La"] * _attn_params(D) \
        + D["L"] * moe


def _ssd_prefill_flops(D) -> float:
    """Per head and token of the chunked SSD."""
    Q, N, P = D["Q"], D["N"], D["P"]
    return float((Q + 1) * (N + P) + 4 * N * P)


def task_flops(spec, batch: int, prompt_len: int, new_tokens: int) -> float:
    """Model FLOPs of one ``generate`` task on this chip's share."""
    D = _dims(spec)
    S, n = prompt_len, new_tokens - 1
    flops = 2.0 * _per_token_params(spec) * (S + n)
    flops += 2.0 * D["d"] * D["V"] * new_tokens
    flops += D["Lm"] * D["mh"] * (_ssd_prefill_flops(D) * S
                                  + 5.0 * D["P"] * D["N"] * n)
    positions = S * (S + 1) // 2 + sum(p + 1 for p in range(S, S + n))
    flops += D["La"] * 4.0 * D["H"] * D["hd"] * positions
    return batch * flops


def ssd_scan_work(spec, batch: int, prompt_len: int):
    """(FLOPs, bytes) of the chunked SSD scan over the Mamba2 layers of a
    prefill, at the configuration's chunk size. Bytes: x and y in
    bfloat16, dt in float32, B and C in bfloat16, the final state in
    float32."""
    D = _dims(spec)
    S, mh, P, N, G = prompt_len, D["mh"], D["P"], D["N"], D["G"]
    flops = D["Lm"] * batch * mh * float(S) * _ssd_prefill_flops(D)
    nbytes = D["Lm"] * batch * (S * mh * (2 * P * 2 + 4) + S * 2 * G * N * 2
                                + mh * P * N * 4.0)
    return flops, nbytes
