"""Operations and bytes of the model's work, from the configuration's shapes.

The configuration's family (``families/<model_type>.py``) says what one
token passes through: ``token_matmuls(conf)``, the (K, N) of every weight
matmul in order, the unembedding last, and ``attention_call(q_len,
q_offset, conf, peaks)``, one logical layer's attention as a ``Work``.
This module prices the matmuls and sums both into the readers' totals.

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


def padded_vocab(conf: dict) -> int:
    return -(-conf["vocab_size"] // 256) * 256


def logical_layers(conf: dict) -> int:
    return conf["num_hidden_layers"]


def unembedding(conf: dict) -> tuple[int, int]:
    """(K, N) of the unembedding, the last matmul every token passes."""
    return conf["hidden_size"], padded_vocab(conf)


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


def mvm_calls(M: int, family, conf: dict, peaks) -> Work:
    """Every weight matmul of one forward pass of M rows through all
    logical layers and the unembedding (the family's ``token_matmuls``)."""
    w = Work()
    for k, n in family.token_matmuls(conf):
        w.add(mvm_call(M, k, n, peaks))
    return w


def causal_pairs(q_len: int, q_offset: int) -> int:
    """(query, key) pairs causality keeps for q_len queries at absolute
    positions q_offset .. q_offset + q_len - 1 over keys 0 .. itself."""
    return q_len * q_offset + q_len * (q_len + 1) // 2


def attention_calls(q_len: int, q_offset: int, family, conf: dict,
                    peaks) -> Work:
    """The family's attention call of q_len queries at q_offset in every
    logical layer."""
    one = family.attention_call(q_len, q_offset, conf, peaks)
    L = logical_layers(conf)
    return Work(bf16_flops=one.bf16_flops * L, bytes=one.bytes * L,
                min_seconds=one.min_seconds * L)


def ideal_seconds(layer_tokens: int, unembed_rows: int, attention,
                  family, conf: dict, peaks) -> float:
    """Least time of model work: ``layer_tokens`` token passes through
    every logical layer's weight matmuls, ``unembed_rows`` rows through the
    unembedding, and one attention call per ``(q_len, q_offset)`` in
    ``attention`` in every logical layer.  Weight matmuls at the int8
    peak, attention at the bf16 peak; compute only (the whole step's share
    of the chip's peak)."""
    *layers, last = family.token_matmuls(conf)
    if last != unembedding(conf):
        raise ValueError(f"token_matmuls ends in {last}, not the "
                         f"unembedding {unembedding(conf)}")
    k, n = last
    mm = 2.0 * (layer_tokens * sum(a * b for a, b in layers)
                + unembed_rows * k * n)
    att = sum(attention_calls(q, o, family, conf, peaks).bf16_flops
              for q, o in attention)
    return mm / peaks.int8_ops + att / peaks.bf16_flops
