"""Forward FLOPs per sequence of DeepSeek-V2-Lite under split LoRA, from
the configuration's shapes, at two FLOPs per multiply-add.

Per layer: the four MLA projections (``q_proj``, ``kv_a_proj_with_mqa``,
``kv_b_proj``, ``o_proj``) and their LoRA updates on every token; causal
attention, scores and values over the s(s+1)/2 (query, key) pairs; then the
dense SwiGLU FFN (layer 0), or the router, the shared experts, and the
routed experts at the expected share of a token's top-k pairs that land on
the held experts (top_k x n_routed_experts / router_experts rows a token).
Then the LM head over the vocabulary slice.  Norms, rotary, softmax and
the embedding gather add under 1%.  At the cell's shapes: 557.8 GFLOP a
sequence of 1024 tokens (545 MFLOP a token)."""


def forward_flops_per_sample(cfg) -> float:
    s, d = cfg["seq_len"], cfg["hidden_size"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rank = cfg["model_kwargs"]["lora_rank"]
    proj = [(d, h * (nope + rope)), (d, r + rope), (r, h * (nope + v)),
            (h * v, d)]
    attn = s * sum(2 * i * o + 2 * rank * (i + o) for i, o in proj)
    attn += 2 * (s * (s + 1) // 2) * h * (nope + rope + v)
    ffe = cfg["moe_intermediate_size"]
    dense = 2 * 3 * d * cfg["intermediate_size"] * s
    moe = s * (2 * d * cfg["router_experts"]
               + 2 * 3 * d * cfg["n_shared_experts"] * ffe
               + 2 * 3 * d * ffe * cfg["num_experts_per_tok"]
               * cfg["n_routed_experts"] / cfg["router_experts"])
    n = cfg["num_hidden_layers"]
    k = cfg["first_k_dense_replace"]
    return (n * attn + k * dense + (n - k) * moe
            + 2 * d * cfg["vocab_size"] * s)
