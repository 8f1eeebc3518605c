"""Generative serving tests — the engine behind a server.

``POST /generate`` streams the engine's tokens as ndjson, a router's stream
is the sequential transcript, and — chaos-marked — a replica killed
mid-stream resumes on a survivor with zero duplicated or dropped tokens.

All CPU-only, on the tiny transformer LM of ``generation_lm.py``.
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import serving

from generation_lm import (S, SPEC, _NET, _PARAMS, _prompts,
                           _sequential_reference)

# ---------------------------------------------------------------------------
# serving integration: server + HTTP streaming + router
# ---------------------------------------------------------------------------

def _server(**kw):
    return serving.InferenceServer(
        _NET, dict(_PARAMS), {"data": (2, S), "softmax_label": (2, S)},
        generator_spec=dict(SPEC), **kw)


def test_server_http_generate_streams_ndjson():
    srv = _server()
    try:
        prompt = [3, 11, 7]
        ref = srv.submit_generate(prompt, 8).result(timeout=60)
        host, port = srv.serve_http()
        req = urllib.request.Request(
            "http://%s:%d/generate" % (host, port),
            data=json.dumps({"prompt": prompt,
                             "max_new_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        toks, done = [], None
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"] == "application/x-ndjson"
            for line in resp:
                obj = json.loads(line)
                if obj.get("done"):
                    done = obj
                    break
                toks.append(obj["token"])
        assert toks == ref
        assert done["n"] == len(ref) and done["ttft_ms"] > 0
    finally:
        srv.stop()


def test_http_generate_404_without_generator():
    srv = serving.InferenceServer(
        _NET, dict(_PARAMS), {"data": (2, S), "softmax_label": (2, S)})
    try:
        host, port = srv.serve_http()
        req = urllib.request.Request(
            "http://%s:%d/generate" % (host, port),
            data=json.dumps({"prompt": [1], "max_new_tokens": 2}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_router_generate_stream_parity():
    rng = np.random.RandomState(13)
    srvs = [_server() for _ in range(2)]
    router = serving.Router(srvs, seed=2)
    try:
        for p in _prompts(rng, 3):
            ref = _sequential_reference(_PARAMS, [(p, 7)])[0]
            assert list(router.generate(p, 7)) == ref
        snap = router.metrics.snapshot()
        assert snap["streams"].get("generate") == 3
    finally:
        router.close()
        for s in srvs:
            s.stop()


@pytest.mark.chaos
def test_router_resumes_stream_after_replica_kill():
    """Kill the replica actively decoding mid-stream: the Router
    re-submits prompt + tokens-so-far on a survivor and the client sees
    one uninterrupted, bit-identical token stream."""
    prompt = [5, 9, 2]
    ref = _sequential_reference(_PARAMS, [(prompt, 12)])[0]
    srvs = [_server() for _ in range(2)]
    router = serving.Router(srvs, seed=3)
    try:
        out, killed = [], False
        for tok in router.generate(prompt, 12):
            out.append(tok)
            if len(out) == 4 and not killed:
                killed = True
                victim = next(s for s in srvs
                              if s._generator.active_lanes() > 0)
                threading.Thread(target=victim.stop,
                                 kwargs={"drain": False}).start()
        assert out == ref
        assert router.metrics.snapshot()["stream_resumes"] >= 1
    finally:
        router.close()
        for s in srvs:
            s.stop()
