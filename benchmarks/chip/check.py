"""What decides ``correct``: served tokens against the plain reference.

After the window, a sample of the finished requests is run through the
plain reference of the configuration's family (``families/``) once,
prompt and served tokens together: the longest, and one request served
in each slot of the decode batch, drawn from the seed, so that a fault in
any part of the batch reaches the comparison.  At each
position that produced a served token, the gap is the reference's best
logit minus the reference's logit of the token the program served: 0
where the program chose the reference's argmax, larger the worse its
choice.  The number compared is the widest gap over every sampled token
(``max_logit_gap``).

The control puts the reference, in a lower precision (``bits``), in the
program's place: at the same positions it reads the gap of the token that
the lower precision ranks first.
"""
from __future__ import annotations

import numpy as np


def sample(done: dict, slot_of: dict, seed: int):
    """Completions to check: the longest (prompt plus served), then one
    other from each slot that finished one, drawn from the seed, in slot
    order.  ``slot_of`` maps a request to the slot that served it."""
    if not done:
        return []
    comps = sorted(done.values(), key=lambda c: c.rid)
    longest = max(comps, key=lambda c: (len(c.tokens), -c.rid))
    by_slot: dict[int, list] = {}
    for c in comps:
        if c.rid != longest.rid:
            by_slot.setdefault(slot_of[c.rid], []).append(c)
    rng = np.random.default_rng([seed, 2])
    return [longest] + [by_slot[s][rng.integers(len(by_slot[s]))]
                        for s in sorted(by_slot)]


def slots_covered(comps, slot_of: dict) -> int:
    return len({slot_of[c.rid] for c in comps})


def positions(comp) -> np.ndarray:
    """Rows whose logits chose the served tokens: P - 1 .. P + n - 2."""
    n = len(comp.tokens) - comp.prompt_len
    return np.arange(comp.prompt_len - 1, comp.prompt_len - 1 + n)


def served(comps) -> np.ndarray:
    return np.concatenate([c.tokens[c.prompt_len:] for c in comps])


def seqs_of(comps):
    # the last served token is never an input
    return [c.tokens[:-1] for c in comps]


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    ref_logits = np.asarray(ref_logits, np.float32)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]


def reference_gaps(family, conf: dict, seed: int, comps, bits=None,
                   shape=None):
    """(gaps of the served tokens, gaps of the control's choices or None)
    at every served position of ``comps``, by ``family.logits``; ``shape``
    is the reference's fixed (sequences, length)."""
    seqs, rows = seqs_of(comps), [positions(c) for c in comps]
    ref = np.asarray(family.logits(conf, seed, seqs, rows, shape=shape),
                     np.float32)
    program = gaps(ref, served(comps))
    control = None
    if bits:
        low = np.asarray(family.logits(conf, seed, seqs, rows, bits=bits,
                                       shape=shape))
        control = gaps(ref, low.argmax(axis=-1))
    return program, control
