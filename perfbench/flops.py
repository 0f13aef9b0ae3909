"""Operations the algorithm needs, from shapes alone.

Counts are of useful work: padding, dummy training slots and the masked
half of causal attention are left out, so a share of the peak computed
from them cannot pass 100% by counting waste.
"""
from __future__ import annotations


def proxy_forward_flops_per_row(proxy: dict, with_projector: bool) -> float:
    """One row through the proxy MLP (embed -> hidden^(layers-1) -> latent),
    plus the training-only projector head."""
    dims = ([proxy["embed_dim"]] + [proxy["hidden_dim"]] * (proxy["num_layers"] - 1)
            + [proxy["latent_dim"]])
    f = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    if with_projector:
        f += 2 * proxy["latent_dim"] * proxy["proj_dim"]
    return float(f)


def proxy_train_flops_per_leaf(proxy: dict) -> float:
    """Two-phase training of one leaf's proxy: every step runs forward and
    backward (3x the forward) over the batch's documents and the query."""
    steps = proxy["phase1_steps"] + proxy["phase2_steps"]
    rows = proxy["batch_size"] + 1
    return 3.0 * steps * rows * proxy_forward_flops_per_row(proxy, True)


def proxy_score_flops_per_leaf(proxy: dict, n_docs: int) -> float:
    """Scoring one leaf over the whole collection: the MLP over every
    document and the cosine against the query latent."""
    return n_docs * (proxy_forward_flops_per_row(proxy, False)
                     + 2.0 * proxy["latent_dim"])


def cold_leaf_flops(proxy: dict, n_docs: int) -> float:
    """A leaf that is trained and scored from nothing."""
    return proxy_train_flops_per_leaf(proxy) + proxy_score_flops_per_leaf(proxy, n_docs)


def llama_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Prefill of a Llama-architecture decoder, per token, at sequence
    length ``seq_len``: the projections, the gated MLP and causal
    attention (on average a token attends to (seq_len + 1) / 2 keys).
    No LM head: the embedding pass pools hidden states."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nq
    proj = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    mlp = 3 * d * f
    attn = 2 * 2 * nq * hd * (seq_len + 1) / 2.0
    return cfg["num_hidden_layers"] * (2.0 * (proj + mlp) + attn)
