"""Out-of-process serving: engine + HTTP frontend in a SUBPROCESS, driven
by concurrent clients over real sockets.

Reference analog (unverified — mount empty): ``scala/serving/`` decouples
the serving engine from clients via Flink/Redis processes; these specs
prove the TPU-native stack holds up across a process boundary — dynamic
batching under concurrency and bounded-queue backpressure (non-blocking
shed + client retry, never an unbounded block).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from urllib import request as urlreq

import numpy as np
import pytest

SERVER = textwrap.dedent("""
    import sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu import nn
    from bigdl_tpu.serving.inference_model import InferenceModel
    from bigdl_tpu.serving.server import ServingConfig, ServingServer
    from bigdl_tpu.serving.http_frontend import HttpFrontend

    model = nn.Sequential([nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4)])
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.float32))
    im = InferenceModel(model, variables)
    srv = ServingServer(im, ServingConfig(batch_size=16,
                                          batch_timeout_s=0.01,
                                          queue_capacity=64)).start()
    fe = HttpFrontend(srv, port=0).start()
    print(f"URL={fe.url}", flush=True)
    sys.stdin.readline()        # parent closes stdin to stop us
    fe.stop(); srv.stop()
    print(f"STATS={srv.stats['batches']},{srv.stats['requests']}",
          flush=True)
""")


def _post(url, payload, timeout=30.0):
    req = urlreq.Request(url, data=json.dumps(payload).encode(),
                         headers={"Content-Type": "application/json"})
    with urlreq.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.mark.slow
def test_serving_subprocess_concurrent_clients(tmp_path):
    script = tmp_path / "server.py"
    script.write_text(SERVER)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in [repo_root, os.environ.get("PYTHONPATH")] if p)
    env = dict(os.environ, PYTHONPATH=pythonpath, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("URL="), line
        url = line[4:] + "/predict"

        rs = np.random.RandomState(0)
        n_clients, n_requests = 8, 20
        errors = []

        def client():
            try:
                for _ in range(n_requests):
                    x = rs.rand(2, 8).astype(np.float32)
                    out = _post(url, {"instances": x.tolist()})
                    preds = np.asarray(out["predictions"])
                    assert preds.shape == (2, 4), preds.shape
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors

        # health endpoint reports engine stats across the process boundary
        with urlreq.urlopen(line[4:] + "/health", timeout=10) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        total = n_clients * n_requests
        assert health["requests"] == total, health
        # concurrency => dynamic batching actually coalesced requests
        assert health["batches"] < total, health
    finally:
        if proc.poll() is None:
            proc.stdin.close()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    out_rest = proc.stdout.read()
    assert "STATS=" in out_rest, out_rest


def test_bounded_queue_backpressure():
    """The request queue is BOUNDED and admission never blocks: when the
    engine falls behind, enqueue sheds (``ServiceUnavailableError``) and
    the producer retries — every ACCEPTED request still completes."""
    from bigdl_tpu.serving.inference_model import InferenceModel
    from bigdl_tpu.serving.server import (ServiceUnavailableError,
                                          ServingConfig, ServingServer)

    def slow_predict(x):
        time.sleep(0.02)
        return x * 2.0

    im = InferenceModel(predict_fn=slow_predict)
    srv = ServingServer(im, ServingConfig(batch_size=4,
                                          batch_timeout_s=0.001,
                                          queue_capacity=4)).start()
    try:
        seen_qsize = []
        rids = []
        retries = [0]
        lock = threading.Lock()

        def producer(k):
            for i in range(10):
                payload = np.full((1, 3), float(k * 10 + i), np.float32)
                while True:        # shed -> bounded client-side retry
                    try:
                        rid = srv.enqueue(payload)
                        break
                    except ServiceUnavailableError as e:
                        with lock:
                            retries[0] += 1
                        time.sleep(min(e.retry_after, 0.01))
                with lock:
                    rids.append(rid)
                    seen_qsize.append(srv._in.qsize())

        threads = [threading.Thread(target=producer, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert max(seen_qsize) <= 4, max(seen_qsize)
        for rid in rids:
            res = srv.query(rid, timeout=30)
            assert res.shape == (1, 3)
        assert srv.stats["requests"] == 40
        # the bounded queue actually pushed back on the producers
        assert retries[0] > 0
        assert srv.stats["shed_requests"] == retries[0]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# multi-WORKER scale-out: N process-isolated engines behind one round-robin
# proxy with supervision (the Flink task-manager posture)

def _pool_loader():
    """Worker-side model factory (resolved as tests.test_serving_multiproc:
    _pool_loader in the worker's own interpreter)."""
    import numpy as np
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.serving.inference_model import InferenceModel

    model = nn.Sequential([nn.Linear(8, 4)])
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.float32))
    return InferenceModel(model, variables)


@pytest.mark.slow
def test_serving_pool_scaleout_and_supervision():
    from bigdl_tpu.serving.pool import ServingPool

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in [repo_root, os.environ.get("PYTHONPATH")] if p)
    env = {"PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu"}
    pool = ServingPool("tests.test_serving_multiproc:_pool_loader",
                       workers=2, batch_size=8, worker_env=env,
                       supervise_interval_s=0.3)
    pool.start()
    try:
        rs = np.random.RandomState(0)

        def many(n):
            for _ in range(n):
                x = rs.rand(2, 8).astype(np.float32)
                out = _post(pool.url + "/predict", {"instances": x.tolist()})
                assert np.asarray(out["predictions"]).shape == (2, 4)

        many(12)
        with urlreq.urlopen(pool.url + "/health", timeout=10) as r:
            health = json.loads(r.read())
        assert health["requests"] == 12
        per_worker = [int(w.get("requests", 0)) for w in health["workers"]]
        # round-robin actually spread load over BOTH workers
        assert all(p > 0 for p in per_worker), per_worker

        # supervision: kill one worker; requests keep succeeding (the
        # proxy skips the corpse) and the supervisor respawns it
        victim = pool.workers[0]
        victim.proc.kill()
        victim.proc.wait(timeout=10)
        many(6)                      # served by the survivor
        deadline = time.time() + 60
        while time.time() < deadline and not (victim.alive()
                                              and pool.restarts >= 1):
            time.sleep(0.2)
        assert pool.restarts >= 1
        assert all(w.alive() for w in pool.workers)
        many(6)                      # both workers back in rotation
    finally:
        pool.stop()
