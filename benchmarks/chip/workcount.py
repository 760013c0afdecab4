"""Operations and bytes of the model's work, from the configuration's shapes.

Everything here is the *logical* work: a matmul is 2*M*K*N operations on
its shapes, and attention is counted over the (query, key) pairs causality
keeps, whatever kernel implements either.  Under a PRM reuse plan every
logical layer counts its own pass and its own weight read: 32 logical
layers of 8 physical blocks read a weight 32 times per token.

Weight matmuls run W8A8 (int8 operands): their operations are timed
against the int8 peak, their weights are read as int8 (one byte per
parameter), their activations and outputs move as bf16.  Attention runs
in bf16 and is timed against the bf16 peak.
"""
from __future__ import annotations

import dataclasses

ACT_BYTES = 2          # bf16 activations, outputs, K/V
WEIGHT_BYTES = 1       # int8 bank


def layer_matmuls(conf: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of each weight matmul of one logical layer."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w_gate", d, ff), ("w_up", d, ff), ("w_down", ff, d)]


def padded_vocab(conf: dict) -> int:
    return -(-conf["vocab_size"] // 256) * 256


def logical_layers(conf: dict) -> int:
    return conf["num_hidden_layers"]


def params_per_layer(conf: dict) -> int:
    return sum(k * n for _, k, n in layer_matmuls(conf))


def token_matmuls(conf: dict) -> list[tuple[int, int]]:
    """(K, N) of every weight matmul one token passes through, in order:
    every logical layer's, then the unembedding."""
    one = [(k, n) for _, k, n in layer_matmuls(conf)]
    return (one * logical_layers(conf)
            + [(conf["hidden_size"], padded_vocab(conf))])


def matmul_params_per_token(conf: dict) -> int:
    return sum(k * n for k, n in token_matmuls(conf))


@dataclasses.dataclass
class Work:
    """Operations and bytes of a set of calls, split by the peak that
    times them."""
    int8_ops: float = 0.0
    bf16_flops: float = 0.0
    bytes: float = 0.0
    min_seconds: float = 0.0      # sum over calls of each call's bound

    def add(self, other: "Work") -> None:
        self.int8_ops += other.int8_ops
        self.bf16_flops += other.bf16_flops
        self.bytes += other.bytes
        self.min_seconds += other.min_seconds


def mvm_call(M: int, K: int, N: int, peaks) -> Work:
    """One W8A8 matmul of M rows: 2MKN int8 operations; int8 weight bytes
    K*N plus bf16 input and output bytes.  Its least time is the larger of
    operations over the int8 peak and bytes over HBM bandwidth."""
    ops = 2.0 * M * K * N
    nbytes = WEIGHT_BYTES * K * N + ACT_BYTES * (M * K + M * N)
    return Work(int8_ops=ops, bytes=nbytes,
                min_seconds=max(ops / peaks.int8_ops,
                                nbytes / peaks.hbm_bytes))


def mvm_calls(M: int, conf: dict, peaks) -> Work:
    """Every weight matmul of one forward pass of M rows through all
    logical layers and the unembedding."""
    w = Work()
    for k, n in token_matmuls(conf):
        w.add(mvm_call(M, k, n, peaks))
    return w


def causal_pairs(q_len: int, q_offset: int) -> int:
    """(query, key) pairs causality keeps for q_len queries at absolute
    positions q_offset .. q_offset + q_len - 1 over keys 0 .. itself."""
    return q_len * q_offset + q_len * (q_len + 1) // 2


def attention_call(q_len: int, q_offset: int, conf: dict, peaks) -> Work:
    """One layer's causal attention of q_len queries at q_offset: 4 * H *
    head_dim FLOPs per kept pair (QK^T and PV); bytes: q and o of the
    queries, k and v of the keys up to the last query."""
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    flops = 4.0 * H * hd * causal_pairs(q_len, q_offset)
    nbytes = ACT_BYTES * hd * (2 * q_len * H + 2 * (q_offset + q_len) * KV)
    return Work(bf16_flops=flops, bytes=nbytes,
                min_seconds=max(flops / peaks.bf16_flops,
                                nbytes / peaks.hbm_bytes))


def attention_calls(q_len: int, q_offset: int, conf: dict, peaks) -> Work:
    """The same attention call in every logical layer."""
    one = attention_call(q_len, q_offset, conf, peaks)
    L = logical_layers(conf)
    return Work(bf16_flops=one.bf16_flops * L, bytes=one.bytes * L,
                min_seconds=one.min_seconds * L)


def ideal_seconds(layer_tokens: int, unembed_rows: int, attn_pairs: int,
                  conf: dict, peaks) -> float:
    """Least time of model work: ``layer_tokens`` token passes through
    every logical layer's weight matmuls, ``unembed_rows`` rows through the
    unembedding, and attention keeping ``attn_pairs`` (query, key) pairs
    per layer in all.  Weight matmuls at the int8 peak, attention at the
    bf16 peak; compute only (the whole step's share of the chip's peak)."""
    layer = sum(k * n for _, k, n in layer_matmuls(conf))
    mm = 2.0 * (layer_tokens * layer * logical_layers(conf)
                + unembed_rows * conf["hidden_size"] * padded_vocab(conf))
    att = (4.0 * conf["num_attention_heads"] * conf["head_dim"] * attn_pairs
           * logical_layers(conf))
    return mm / peaks.int8_ops + att / peaks.bf16_flops
