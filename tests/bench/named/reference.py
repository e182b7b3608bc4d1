"""A stand-in for a configuration's own plain reference, for the tests of
the seam only (tests/bench/test_bench_named.py): it is found by the name
`tests/bench/named/config.json` gives it, and what it returns could come
from nowhere else. It is no model: every served token lies below the
reference's best by exactly `rope_scaling.factor`, a nested key of the
configuration's file, so `gap_max` and `gap_mean` both read that number
when this module decided them and the nested key arrived. A control
(`lower`) moves the best logit `shift` columns further."""

import numpy as np


def logits_for(samples, cfg, pad_to, lower=None):
    best = float(cfg["rope_scaling"]["factor"])
    shift = 1 + int((lower or {}).get("shift", 0))
    assert all(len(s["prompt"]) + len(s["served"]) <= pad_to
               for s in samples)
    out = []
    for s in samples:
        served = np.asarray(s["served"])
        logits = np.zeros((len(served), cfg["vocab_size"]), np.float32)
        logits[np.arange(len(served)),
               (served + shift) % cfg["vocab_size"]] = best
        out.append(logits)
    return out
