"""A family that is not in ``families/``: the llama family's program and
reference, with a work count of its own and a tally of every call, so a
test sees which of the harness's paths reach the cell's family."""
from __future__ import annotations

import collections

import workcount
from cell import load_family

CALLS: collections.Counter = collections.Counter()
_llama = load_family("llama")


def program_config(conf):
    CALLS["program_config"] += 1
    return _llama.program_config(conf)


def logits(conf, seed, seqs, rows, bits=None, shape=None):
    CALLS["logits"] += 1
    return _llama.logits(conf, seed, seqs, rows, bits, shape)


def token_matmuls(conf):
    CALLS["token_matmuls"] += 1
    d = conf["hidden_size"]
    return [(d, 3 * d), (3 * d, d), workcount.unembedding(conf)]


def attention_call(q_len, q_offset, conf, peaks):
    CALLS["attention_call"] += 1
    flops = 1000.0 * q_len + q_offset
    return workcount.Work(bf16_flops=flops, bytes=0.0,
                          min_seconds=flops / peaks.bf16_flops)
