"""What decides `correct`: the answers the timed flushes produced, and the
query decisions made from them, against the plain reference.

`Recorder` wraps the session's runtime backend and keeps, for every tuple
a window flush scored, the answer it returned (the last execution's
answer wins: every execution of a prepared plan over the same corpus
scores the same tuples). After the window, `Comparison` takes a sample of
documents from the seed, the longest among them, recomputes every answer
the program gave for them with the reference, and replays the plan's
cascade on the reference's answers.

Numbers compared (each beside its limit in the run's output):

  score_gap   largest gap between a served answer and the reference's,
              over the sampled tuples, as a share of the reference's own
              range for that kind of answer: |log-odds - ref| for filters;
              for maps the larger of the reference logit the served value
              gives up against the reference's best, and the gap between
              the served and the reference margin
  firm_flips  sampled documents whose final decision (accepted, extracted
              value) differs from the reference's replay, where every
              reference score along the replay lies further than the
              score_gap limit (in range units) from the boundary it met
"""
from __future__ import annotations

import threading
from collections import Counter
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


class Recorder:
    """A runtime Backend that forwards to the session's backend and, while
    `on`, records each scored tuple's answer and counts how often each
    (operator, tuple) was scored (for the per-layer operation counts)."""

    def __init__(self, inner):
        self.inner = inner
        self.name = getattr(inner, "name", "recorder")
        self.records: Dict[Tuple[int, str, int], Any] = {}
        self.scored: Counter = Counter()
        self.on = False
        self._lock = threading.Lock()

    def candidates(self, op):
        return self.inner.candidates(op)

    def resolve(self, op, op_name):
        return self.inner.resolve(op, op_name)

    def kv_bytes_loaded(self):
        return self.inner.kv_bytes_loaded()

    def transfer_stats(self):
        return self.inner.transfer_stats()

    def score_filter(self, op, op_name, items):
        out = self.inner.score_filter(op, op_name, items)
        if self.on:
            with self._lock:
                self.scored.update((op_name, it.item_id) for it in items)
                for it, s in zip(items, np.asarray(out)):
                    self.records[(op.task_id, op_name, it.item_id)] = \
                        float(s)
        return out

    def run_map(self, op, op_name, items):
        vals, conf = self.inner.run_map(op, op_name, items)
        if self.on:
            with self._lock:
                self.scored.update((op_name, it.item_id) for it in items)
                for it, v, c in zip(items, np.asarray(vals),
                                    np.asarray(conf)):
                    self.records[(op.task_id, op_name, it.item_id)] = \
                        (int(v), float(c))
        return vals, conf


def sample_docs(docs, n: int, rng) -> List[Any]:
    """The longest document and n - 1 others drawn from `rng`."""
    longest = max(range(len(docs)), key=lambda i: len(docs[i].tokens))
    rest = [i for i in range(len(docs)) if i != longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [docs[longest]] + [docs[rest[i]] for i in sorted(pick)]


class RefScorer:
    """Reference answers per (document, rung): one prefill per document,
    one decode of every template query token per rung."""

    def __init__(self, ref, keep_count: Callable, params, traffic: Dict,
                 docs, s_ref: int):
        import jax.numpy as jnp
        self.jnp, self.ref, self.params = jnp, ref, params
        self.keep_count, self.s_ref = keep_count, s_ref
        ops = traffic["template"]
        self.qtok = [op["query_token"] for op in ops]
        self.answer = sorted({a for op in ops for a in op["answer_tokens"]})
        # calibration: the first documents, padded together to a multiple
        # of 16 with token 0; the trailing half of that length
        calib = docs[:traffic["calib_docs"]]
        s_c = -(-max(len(d.tokens) for d in calib) // 16) * 16
        toks = np.zeros((len(calib), s_ref), np.int32)
        for i, d in enumerate(calib):
            toks[i, :len(d.tokens)] = d.tokens
        self.mu, self.sig2 = ref.calib(params, jnp.asarray(toks),
                                       int(s_c * 0.5), s_c)
        self._kv_id, self._kv = None, None
        self._ans: Dict[Tuple[int, float, bool], np.ndarray] = {}

    def answers(self, doc, ratio: float, quant: bool) -> Dict[int, float]:
        """{answer token: logit} per template query token (list)."""
        key = (doc.item_id, ratio, quant)
        if key not in self._ans:
            jnp = self.jnp
            if self._kv_id != doc.item_id:
                toks = np.zeros((1, self.s_ref), np.int32)
                toks[0, :len(doc.tokens)] = doc.tokens
                k, v = self.ref.prefill(self.params, jnp.asarray(toks))
                self._kv_id, self._kv = doc.item_id, (k[:, 0], v[:, 0])
            k, v = self._kv
            n = len(doc.tokens)
            keep = self.keep_count(ratio, n)
            kept = self.ref.keep_mask(k, self.mu, self.sig2, n, keep)
            out = self.ref.decode(self.params, k, v, kept,
                                  jnp.asarray(keep, jnp.int32),
                                  jnp.asarray(self.qtok, jnp.int32),
                                  jnp.asarray(self.answer, jnp.int32),
                                  quant=quant)
            self._ans[key] = np.asarray(out, np.float64)
        return [dict(zip(self.answer, row)) for row in self._ans[key]]


def _filter_score(logits: Dict[int, float], op: Dict) -> float:
    yes, no = op["answer_tokens"]
    return logits[yes] - logits[no]


def _map_answer(logits: Dict[int, float], op: Dict):
    vals = np.asarray([logits[t] for t in op["answer_tokens"]])
    order = np.argsort(-vals, kind="stable")
    return (op["answer_tokens"][order[0]], float(vals[order[0]]
                                                 - vals[order[1]]),
            float(vals.max() - vals.min()))


class Comparison:
    """Reference answers for the sampled tuples, and the numbers that
    compare another source of answers with them."""

    def __init__(self, scorer: RefScorer, traffic: Dict, plan, query,
                 resolve: Callable, sample, records: Dict):
        self.scorer, self.plan, self.sample = scorer, plan, sample
        self.ops = traffic["template"]
        self.sem_ops = list(query.semantic_ops)
        self.op_index = {op["task_id"]: i for i, op in enumerate(self.ops)}
        self.rung: Dict[str, Tuple[float, bool]] = {}
        for st in plan.stages:
            phys = resolve(self.sem_ops[st.logical_idx], st.op_name)
            self.rung[st.op_name] = (float(phys.ratio), bool(phys.quant))
        ids = {d.item_id for d in sample}
        self.tuples = sorted(k for k in records if k[2] in ids)
        self.docs = {d.item_id: d for d in sample}
        # the reference's answer, and each kind's range over the sample
        self.ref_ans = {k: self._ref(k) for k in self.tuples}
        f = [abs(a) for k, a in self.ref_ans.items()
             if not isinstance(a, tuple)]
        m = [a[2] for a in self.ref_ans.values() if isinstance(a, tuple)]
        self.scale = {"filter": max([1.0] + f), "map": max([1.0] + m)}

    def _ref(self, key, scorer=None):
        task, op_name, item = key
        op = self.ops[self.op_index[task]]
        logits = (scorer or self.scorer).answers(
            self.docs[item], *self.rung[op_name])[self.op_index[task]]
        if op["op"] == "sem_filter":
            return _filter_score(logits, op)
        return _map_answer(logits, op)

    def score_gap(self, answers: Dict) -> float:
        """Largest gap of `answers` (same keys as the records) from the
        reference, in range units."""
        worst = 0.0
        for key in self.tuples:
            got, want = answers[key], self.ref_ans[key]
            if not isinstance(want, tuple):
                worst = max(worst, abs(got - want) / self.scale["filter"])
                continue
            task, op_name, item = key
            op = self.ops[self.op_index[task]]
            logits = self.scorer.answers(self.docs[item], *self.rung[
                op_name])[self.op_index[task]]
            best = max(logits[t] for t in op["answer_tokens"])
            served_val, served_margin = got
            gap = max(best - logits.get(served_val, -np.inf),
                      abs(served_margin - want[1]))
            worst = max(worst, gap / self.scale["map"])
        return worst

    def replay(self, doc, scorer=None) -> Tuple[bool, Dict[int, int], float]:
        """The plan's cascade on one document with reference answers:
        (accepted, committed map values, smallest distance to a decision
        boundary along the way, in range units)."""
        n = len(self.sem_ops)
        is_map = [self.ops[self.op_index[o.task_id]]["op"] == "sem_map"
                  for o in self.sem_ops]
        unsure = [True] * n
        acc, rej = [False] * n, [False] * n
        vals = {li: 0 for li in range(n) if is_map[li]}
        margin = np.inf
        for st in self.plan.stages:
            li = st.logical_idx
            if not unsure[li] or any(rej[lj] for lj in range(n)
                                     if lj != li and not is_map[lj]):
                continue
            task = self.sem_ops[li].task_id
            a = self._ref((task, st.op_name, doc.item_id), scorer)
            if not is_map[li]:
                sc = self.scale["filter"]
                if st.is_gold:
                    ok, no = a > 0, a <= 0
                    margin = min(margin, abs(a) / sc)
                else:
                    z_acc, z_rej = a - st.thr_hi, st.thr_lo - a
                    ok = z_acc > 0 and z_acc >= z_rej
                    no = z_rej > 0 and z_rej > z_acc
                    edges = [st.thr_hi, st.thr_lo,
                             0.5 * (st.thr_hi + st.thr_lo)]
                    margin = min(margin, min(abs(a - e) for e in edges) / sc)
                acc[li] |= ok
                rej[li] |= no
                unsure[li] = not (ok or no)
            else:
                sc = self.scale["map"]
                val, m, _ = a
                commit = st.is_gold or m - st.thr_hi > 0
                if not st.is_gold:
                    margin = min(margin, abs(m - st.thr_hi) / sc)
                if commit:
                    margin = min(margin, m / sc)
                    vals[li] = val
                    unsure[li] = False
        accepted = all(acc[li] for li in range(n) if not is_map[li])
        return accepted, vals, margin

    def firm_flips(self, decisions: Dict[int, Tuple[bool, Dict]],
                   limit: float, scorer=None) -> int:
        """Sampled documents whose decision in `decisions` differs from
        the reference replay where the replay is firm. With `scorer`,
        `decisions` are ignored and that scorer's replay is compared."""
        flips = 0
        for doc in self.sample:
            want_acc, want_vals, margin = self.replay(doc)
            if scorer is not None:
                got_acc, got_vals, _ = self.replay(doc, scorer)
            else:
                got_acc, got_vals = decisions[doc.item_id]
            if margin > limit and (got_acc != want_acc or any(
                    int(got_vals.get(li, 0)) != int(v)
                    for li, v in want_vals.items())):
                flips += 1
        return flips

    def answers_of(self, scorer) -> Dict:
        """Another scorer's answers for the sampled tuples, in the
        records' form (filters: log-odds; maps: (value, margin))."""
        out = {}
        for key in self.tuples:
            a = self._ref(key, scorer)
            out[key] = a if not isinstance(a, tuple) else (a[0], a[1])
        return out
