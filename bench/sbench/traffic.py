"""The one traffic generator: a corpus and a query template from a traffic
file's parameters and a seed.

Every seed gets the same multiset of document lengths (the quantiles of
the file's length distribution), in its own order, with its own tokens:
the work of a run is then the same from seed to seed, and the seed
changes only which document is where and what it says.

Token layout is the planted-item layout of the program's synthetic
corpora (`make_dataset`): per filter task a latent label, whose signal
tokens are planted at free positions when the label is true or on a coin
flip; per map task a latent value with its signal tokens; every other
position a distractor. Distractors are drawn from the ids past the
planted layout's 256, up to the model's vocabulary."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

N_VALUES = 8


def filter_signal_token(k, y, i): return 64 + 8 * k + 4 * y + i
def map_signal_token(k, v): return 192 + 8 * k + v


@dataclass
class Doc:
    item_id: int
    tokens: List[int]
    labels: Dict[int, bool]
    map_vals: Dict[int, int]
    row: Dict[str, Any] = field(default_factory=dict)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, purpose)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The length multiset: the (i + 1/2)/n quantiles of a lognormal with
    the given median and sigma, clipped to [min, max] as ingestion does,
    and rounded up to whole pages of `page` tokens."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    out = np.clip(np.rint(out), spec["min"], spec["max"])
    page = int(spec.get("page", 1))
    return (np.ceil(out / page) * page).astype(np.int64)


def make_corpus(t: Dict, seed: int, vocab_size: int) -> List[Doc]:
    rng = rng_for(seed, 0)
    n = int(t["n_docs"])
    lens = rng.permutation(lengths(t["length"], n))
    n_f, n_m, n_sig = t["n_filter_tasks"], t["n_map_tasks"], t["n_signal"]
    lo = int(t.get("distractor_lo", 256))
    docs = []
    for i, n_tok in enumerate(lens):
        n_tok = int(n_tok)
        labels = {k: bool(rng.random() < 0.45) for k in range(n_f)}
        map_vals = {k: int(rng.integers(N_VALUES)) for k in range(n_m)}
        toks = rng.integers(lo, vocab_size, size=n_tok)
        free = list(rng.permutation(n_tok))
        for k in range(n_f):
            if labels[k] or rng.random() < 0.5:
                y = int(labels[k])
                for p in free[:n_sig]:
                    toks[p] = filter_signal_token(k, y, int(rng.integers(4)))
                free = free[n_sig:]
        for k in range(n_m):
            for p in free[:n_sig]:
                toks[p] = map_signal_token(k, map_vals[k])
            free = free[n_sig:]
        docs.append(Doc(i, [int(x) for x in toks], labels, map_vals,
                        {"length": n_tok}))
    return docs


def frame_for(session, docs, t: Dict):
    """The traffic's query template as a frame over `docs`."""
    tasks = [op["task_id"] for op in t["template"]]
    if len(set(tasks)) != len(tasks):
        raise ValueError(f"template task ids must differ: {tasks}")
    frame = session.frame(docs)
    for op in t["template"]:
        if op["op"] == "sem_filter":
            frame = frame.sem_filter(op["text"], task_id=op["task_id"])
        elif op["op"] == "sem_map":
            frame = frame.sem_map(op["text"], task_id=op["task_id"])
        else:
            raise ValueError(f"unknown template operator {op['op']!r}")
    return frame.with_guarantees(recall=t["recall"],
                                 precision=t["precision"])
