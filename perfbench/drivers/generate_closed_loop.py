"""Serving cells: closed-loop clients against ``POST /generate``.

An in-process ``InferenceServer`` with a generator behind ``serve_http()``;
``clients`` threads on loopback each send their next request when the last
one has finished.  Every token is stamped on arrival at the client.  The
tokens-per-second rate is taken over whole steps: from the first arrival in
the window to the last.  Once the window has closed and the server is gone,
the plain reference scores a seeded sample of the finished requests.
"""
import gc
import http.client
import json
import statistics
import threading
import time

import jax
import numpy as np

from perfbench.harness import rates
from perfbench.harness import trace as _trace
from perfbench.harness import traffic as _traffic


class Clients:
    """The closed loop: one thread a client, stamps on the client side."""

    def __init__(self, host, port, plan):
        self.host, self.port, self.plan = host, port, plan
        self.records, self.lock = [], threading.Lock()
        self.closing = threading.Event()
        self.exhausted = False
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         name="bench-client-%d" % i,
                                         daemon=True)
                        for i in range(len(plan))]

    def start(self):
        for t in self.threads:
            t.start()

    def _client(self, i):
        for r, req in enumerate(self.plan[i]):
            if self.closing.is_set():
                return
            if r == len(self.plan[i]) - 1:
                self.exhausted = True  # the mix needs more rounds
            rec = {"client": i, "round": r, "prompt": req["prompt"],
                   "max_new": req["max_new_tokens"], "tokens": [],
                   "stamps": [], "status": "open",
                   "sent": time.perf_counter()}
            with self.lock:
                self.records.append(rec)
            try:
                self._post(req, rec)
            except Exception as exc:  # counted, never dropped
                rec["error"] = repr(exc)
            if rec["status"] == "open":
                rec["status"] = "cut" if self.closing.is_set() else "failed"
            rec["end"] = time.perf_counter()

    def _post(self, req, rec):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        try:
            conn.request("POST", "/generate", json.dumps(req),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = "HTTP %d %r" % (resp.status,
                                               resp.read()[:200])
                return
            for raw in resp:
                now = time.perf_counter()
                line = json.loads(raw)
                if "token" in line:
                    rec["tokens"].append(int(line["token"]))
                    rec["stamps"].append(now)
                elif line.get("done"):
                    ok = len(rec["tokens"]) == rec["max_new"]
                    rec["status"] = "done" if ok else "failed"
                    return
                else:
                    rec["error"] = "in-band %r" % (line,)
                    return
        finally:
            conn.close()

    def close(self, timeout=30.0):
        for t in self.threads:
            t.join(timeout)


def token_gap_numbers(h, family, finished, mix, prec_control=None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over a seeded sample of finished requests with the
    longest in it.  With ``prec_control`` also the same gap for the tokens a
    lower precision puts first.  Returns (gap, control gap, tokens)."""
    cfg = h.config
    rng = np.random.default_rng(int(h.seed))
    finished = sorted(finished, key=lambda r: (len(r["prompt"])
                                               + len(r["tokens"])))
    k = min(int(mix["check_requests"]), len(finished))
    pick = [finished[-1]]
    rest = finished[:-1]
    for j in rng.permutation(len(rest))[:k - 1]:
        pick.append(rest[int(j)])
    layers = int(cfg["n_layer"])
    length = int(mix["check_len"])
    params = family.make_weights(cfg, h.seed, layers)
    score = family.make_scorer(cfg, layers, length)
    low = family.make_scorer(cfg, layers, length, prec_control) \
        if prec_control else None
    worst, worst_low, n_tok = 0.0, 0.0, 0
    for rec in pick:
        seq = rec["prompt"] + rec["tokens"]
        if len(seq) > length:
            raise ValueError("request of %d tokens exceeds check_len %d"
                             % (len(seq), length))
        ids = np.zeros((1, length), np.int32)
        ids[0, :len(seq)] = seq
        logits = np.asarray(score(params, ids))
        p, n = len(rec["prompt"]), len(rec["tokens"])
        rows = logits[p - 1:p - 1 + n]
        served = np.asarray(rec["tokens"])
        best = rows.max(-1)
        worst = max(worst, float((best - rows[np.arange(n), served]).max()))
        n_tok += n
        if low is not None:
            first = np.asarray(low(params, ids))[p - 1:p - 1 + n].argmax(-1)
            worst_low = max(worst_low,
                            float((best - rows[np.arange(n), first]).max()))
    return worst, (worst_low if low is not None else None), n_tok


def run(h):
    import mxnet_tpu as mx

    cfg, mix, b, family = h.config, h.mix, h.builder, h.family
    ctx = mx.tpu(0)
    h.mark("imports")
    weights = jax.block_until_ready(
        family.make_weights(cfg, h.seed, int(cfg["n_layer"])))
    h.mark("seeded weights")
    params = {k: mx.nd.NDArray(v, ctx) for k, v in weights.items()}
    del weights
    max_seq = int(mix["max_seq_len"])
    srv = mx.serving.InferenceServer(
        b.scoring_symbol(mx, cfg, mix), params,
        {"data": (1, max_seq), "softmax_label": (1, max_seq)}, ctx=ctx,
        buckets=[1], generator_spec=b.generator_spec(cfg, mix))
    del params
    h.mark("server built and warmed")
    eng = srv.generator
    host, port = srv.serve_http()
    plan = _traffic.closed_loop_plan(mix, int(cfg["vocab_size"]), h.seed)
    # the HTTP path end to end once, outside the window
    warm = Clients(host, port, [[{"prompt": plan[0][0]["prompt"][:8],
                                  "max_new_tokens": 2}]])
    warm.start()
    warm.close(120.0)
    if warm.records[0]["status"] != "done":
        raise RuntimeError("warm-up request failed: %r" % (warm.records,))

    def counters():
        m = eng.metrics
        return {"tokens": m.tokens.value, "steps": m.steps.value,
                "admitted": m.admitted.value, "failed": m.failed.value,
                "cold": eng.cold_decode_runs()}

    h.mark("plan, one request over HTTP")
    clients = Clients(host, port, plan)
    seconds = float(h.seconds)
    traced = seconds * float(mix.get("trace_share", 0.3)) if h.trace else 0.0
    tdir = h.trace_dir() if h.trace else None
    if h.trace:
        _trace.start_trace(tdir)
    h.open_window()
    c0 = counters()
    t_open = time.perf_counter()
    clients.start()
    if h.trace:
        with jax.profiler.TraceAnnotation("bench:window"):
            time.sleep(traced)
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    c1 = counters()
    h.close_window()
    clients.closing.set()
    peak = h.memory_peak_bytes()
    srv.stop(drain=False, timeout_ms=20000.0)
    clients.close()
    del srv, eng
    gc.collect()

    if clients.exhausted:
        raise RuntimeError("a client ran out of requests before the window "
                           "closed: the mix needs more rounds")
    recs = clients.records
    arrivals = [t for r in recs for t in r["stamps"]]
    rate, n_tok, t_a, t_b = rates.whole_step_rate(arrivals, t_open, t_close)
    gaps = rates.gaps_in_window([r["stamps"] for r in recs], t_open, t_close)
    finished = [r for r in recs if r["status"] == "done"
                and r["stamps"][-1] <= t_close]
    failed = [r for r in recs if r["status"] == "failed"]
    ttft = [r["stamps"][0] - r["sent"] for r in recs
            if r["stamps"] and r["stamps"][0] <= t_close]
    delta = {k: c1[k] - c0[k] for k in c0}
    print("[window] counted between the first and the last arrival: %d "
          "tokens in %.4f s; engine counters over the window: decode steps "
          "%d, prefills %d, tokens %d; requests finished %d, failed %d, "
          "still in flight %d; inter-token gaps %d"
          % (n_tok, t_b - t_a, delta["steps"], delta["admitted"],
             delta["tokens"], len(finished), len(failed),
             sum(1 for r in recs if r["status"] == "cut"), len(gaps)),
          flush=True)
    for r in failed:
        print("[window] failed request: client %d round %d: %s"
              % (r["client"], r["round"], r.get("error")), flush=True)

    reduced = None
    if h.trace:
        reduced = _trace.reduce(_trace.load(_trace.find_xplane(tdir)))

    h.mark("window, server stopped")
    if finished:
        t0 = time.perf_counter()
        gap, low, n = token_gap_numbers(h, family, finished, mix, h.control)
        h.readings.update(served_token_logit_gap=gap, served_tokens=n,
                          control_served_token_logit_gap=low)
        print("[check] reference scored %d served tokens in %.1f s"
              % (n, time.perf_counter() - t0), flush=True)
    else:
        gap = float("inf")
    h.checks.add("served_token_logit_gap", gap)
    h.checks.add("cold_runs_in_window", float(delta["cold"]))

    return {
        "attempted": len(finished) + len(failed), "failed": len(failed),
        "end_to_end": {"decode_tokens_per_s": rate},
        "memory_peak_bytes": peak,
        "info": {"kind": "generate", "rate": rate, "gaps": gaps,
                 "ttft": ttft, "counters": delta, "trace": reduced,
                 "chips": h.chips, "finished": len(finished),
                 "median_gap": statistics.median(gaps) if gaps else None}}
