"""The yardstick's arithmetic: published peaks, and the operations and bytes an
algorithm needs for one call, computed from its shapes.

Kept with the benchmark so that a PR that claims a gain cannot move it. Every
function returns ``(flops, bytes)`` of the least work the algorithm requires:
a multiply-add counts two operations, and a tensor that must be read or
written counts once, in the type it is stored in.
"""

# Published peaks of one chip, keyed by the ``device_kind`` JAX reports.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
# inter-chip interconnect). Copied from deepspeed_tpu/perf/chip_specs.py
# without its cpu-host placeholder: an unknown kind is an error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16 * 2**30,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}. Add the row with its source; never default.")
    return PEAKS[device_kind]


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_compute, t_memory), ("compute" if t_compute >= t_memory else "memory")


def paged_attention(query_contexts, n_heads, n_kv_heads, head_dim, block_size, dtype_bytes=2):
    """Paged attention over live blocks for ONE layer call.

    ``query_contexts``: one list per sequence in the batch, holding for each of
    its query tokens the number of cached positions it attends to (itself
    included). QK^T and PV are 2 flops x head_dim each per (query, key, head).
    Bytes: the K and V blocks that hold the sequence's longest context, read
    once per sequence (whole blocks: a block is the unit the cache pages in),
    plus q in and out out.
    """
    flops = 0
    nbytes = 0
    for contexts in query_contexts:
        if not contexts:
            continue
        flops += sum(4 * n_heads * head_dim * c for c in contexts)
        blocks = -(-max(contexts) // block_size)
        nbytes += 2 * blocks * block_size * n_kv_heads * head_dim * dtype_bytes
        nbytes += 2 * len(contexts) * n_heads * head_dim * dtype_bytes
    return flops, nbytes


def _attn_pairs(seq_len, causal):
    # (query, key) pairs that are not masked
    return seq_len * (seq_len + 1) // 2 if causal else seq_len * seq_len


def flash_fwd(batch, seq_len, n_heads, n_kv_heads, head_dim, causal=True, dtype_bytes=2):
    """Flash attention forward: S = QK^T and O = PV (2 matmuls). Reads q, k, v,
    writes o and the float32 log-sum-exp row."""
    flops = 2 * 2 * batch * n_heads * head_dim * _attn_pairs(seq_len, causal)
    nbytes = batch * seq_len * head_dim * dtype_bytes * (2 * n_heads + 2 * n_kv_heads)
    nbytes += batch * seq_len * n_heads * 4
    return flops, nbytes


def flash_bwd_dkv(batch, seq_len, n_heads, n_kv_heads, head_dim, causal=True, dtype_bytes=2):
    """dK/dV kernel: recomputes S = QK^T, then dV = P^T dO, dP = dO V^T,
    dK = dS^T Q (4 matmuls). Reads q, k, v, dO and the float32 lse and delta
    rows; writes dK and dV."""
    flops = 4 * 2 * batch * n_heads * head_dim * _attn_pairs(seq_len, causal)
    nbytes = batch * seq_len * head_dim * dtype_bytes * (2 * n_heads + 4 * n_kv_heads)
    nbytes += 2 * batch * seq_len * n_heads * 4
    return flops, nbytes


def flash_bwd_dq(batch, seq_len, n_heads, n_kv_heads, head_dim, causal=True, dtype_bytes=2):
    """dQ kernel: recomputes S = QK^T, then dP = dO V^T, dQ = dS K (3 matmuls).
    Reads q, k, v, dO, lse, delta; writes dQ."""
    flops = 3 * 2 * batch * n_heads * head_dim * _attn_pairs(seq_len, causal)
    nbytes = batch * seq_len * head_dim * dtype_bytes * (3 * n_heads + 2 * n_kv_heads)
    nbytes += 2 * batch * seq_len * n_heads * 4
    return flops, nbytes


# the kernels whose every call has one shape (batch, seq_len, heads, kv heads, head_dim)
KERNEL_COSTS = {"flash_fwd": flash_fwd, "flash_bwd_dkv": flash_bwd_dkv,
                "flash_bwd_dq": flash_bwd_dq}


def train_flops_per_token(n_params, vocab_size, hidden_size, n_layers, seq_len):
    """Model FLOPs per trained token, PaLM appendix B convention (copied from
    bench.py:_flops_per_token): 6 x (parameters outside the input embedding) for
    the forward and backward matmuls, plus 12 x layers x sequence x hidden for
    attention (causal masking not discounted; recomputation not counted)."""
    return 6.0 * (n_params - vocab_size * hidden_size) + 12.0 * n_layers * seq_len * hidden_size
