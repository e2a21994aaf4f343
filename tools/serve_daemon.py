"""Run (or smoke-test) the networked serving daemon.

The operational entry point for ``workflow/daemon.py``: load a versioned
model artifact (``workflow/serialization.py save_artifact``) and serve
it over HTTP/JSON + the length-prefixed socket, with tenant admission
control and zero-downtime hot-swap (``POST /swap``).

Usage:
    # serve an exported artifact until interrupted
    python tools/serve_daemon.py --artifact model.kart --port 8700

    # the `make serve-daemon` smoke: export two demo artifacts, stand up
    # a live daemon, drive both ingresses, verify admission (403/429),
    # healthz generation identity, and a hot-swap UNDER TRAFFIC with
    # zero dropped requests and per-generation bit-identity; exits 0/1.
    python tools/serve_daemon.py --smoke

Wire protocol and knob reference: README "Serving over the network".
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import struct
import sys
import threading
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_demo_pipeline(d: int, seed: int):
    """A small fitted serving chain whose outputs differ per seed — two
    seeds = two distinguishable model generations."""
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures

    return (
        CosineRandomFeatures.create(d, 32, seed=seed)
        .and_then(L2Normalizer())
        .fit()
    )


def http_post(port: int, path: str, body: dict, headers=None, timeout=30,
              retries: int = 4):
    """POST JSON; returns (status, parsed body). stdlib only.

    Retries on connection-level failures (the daemon's ``conn_drop``
    fault site drops the response after serving — the serve chain is
    pure, so re-sending is safe and is exactly what a real client
    does)."""
    import http.client

    last: Exception = ConnectionError("no attempt made")
    for _attempt in range(max(1, retries)):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **(headers or {})},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())
        except (http.client.HTTPException, OSError) as e:
            # Dropped connection (incl. urllib.error.URLError): retry.
            last = e
    raise last


def http_get(port: int, path: str, timeout=30):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class SocketClient:
    """Length-prefixed framed client for the daemon's socket ingress."""

    def __init__(self, port: int, timeout: float = 30.0):
        self._conn = socket.create_connection(("127.0.0.1", port),
                                              timeout=timeout)

    def request(self, doc: dict) -> dict:
        frame = json.dumps(doc).encode()
        self._conn.sendall(struct.pack(">I", len(frame)) + frame)
        header = self._recv_exact(4)
        (length,) = struct.unpack(">I", header)
        return json.loads(self._recv_exact(length))

    def _recv_exact(self, n: int) -> bytes:
        chunks = []
        got = 0
        while got < n:
            chunk = self._conn.recv(n - got)
            if not chunk:
                raise ConnectionError("daemon closed the connection")
            chunks.append(chunk)
            got += len(chunk)
        return b"".join(chunks)

    def close(self):
        try:
            self._conn.close()
        except OSError:
            pass


def run_smoke(d: int = 8, requests: int = 24, out_dir=None) -> dict:
    """The ``make serve-daemon`` flow (also run in-process by
    tests/test_daemon.py): live daemon, both ingresses, admission,
    healthz identity, hot-swap under traffic. Returns a verdict dict."""
    import tempfile

    import numpy as np

    from keystone_tpu.workflow.daemon import ServingDaemon, Tenant
    from keystone_tpu.workflow.serialization import save_artifact

    out_dir = out_dir or tempfile.mkdtemp(prefix="keystone_daemon_smoke_")
    p1 = _build_demo_pipeline(d, seed=0)
    p2 = _build_demo_pipeline(d, seed=1)
    a1 = os.path.join(out_dir, "model_v1.kart")
    a2 = os.path.join(out_dir, "model_v2.kart")
    art1 = save_artifact(p1, a1, feature_shape=(d,), dtype="float32")
    art2 = save_artifact(p2, a2, feature_shape=(d,), dtype="float32")

    tenants = {
        "sk-gold": Tenant("gold-tenant", "sk-gold", qps=10000, tier="gold"),
        "sk-be": Tenant("be-tenant", "sk-be", qps=2, tier="best_effort"),
    }
    rng = np.random.default_rng(0)
    X = rng.normal(size=(4, d)).astype(np.float32)
    ref1 = np.asarray(p1.apply(X).get())
    ref2 = np.asarray(p2.apply(X).get())

    daemon = ServingDaemon(
        artifact=a1, tenants=tenants, devices=1, buckets=(4, 8),
        max_delay_ms=1.0, name="smoke-daemon", gold_deadline_ms=30000,
        swap_token="smoke-swap-token",
    )
    stop = threading.Event()
    traffic_results: list = []
    traffic_errors: list = []

    def traffic():
        # Sustained gold traffic across the swap: every request must get
        # an answer attributable to exactly one generation. An exhausted
        # retry raise is recorded as an error, not a silent thread death
        # — a dead traffic thread would false-green the very
        # zero-dropped gate this smoke exists to check.
        while not stop.is_set():
            try:
                st, doc = http_post(
                    daemon.http_port, "/predict",
                    {"x": X.tolist()}, {"X-API-Key": "sk-gold"},
                )
            except (ConnectionError, TimeoutError, OSError) as e:
                traffic_errors.append(("exc", type(e).__name__))
                continue
            if st == 200:
                traffic_results.append(
                    (doc["generation"],
                     np.asarray(doc["y"], dtype=np.float32))
                )
            else:
                traffic_errors.append((st, doc.get("error")))

    try:
        st0, doc0 = http_post(
            daemon.http_port, "/predict", {"x": X.tolist()},
            {"X-API-Key": "sk-gold", "X-Trace-Id": "smoke-trace-http"},
        )
        http_ok = st0 == 200 and np.array_equal(
            np.asarray(doc0["y"], np.float32), ref1
        )
        # Wire trace context round-trips both ingresses: the id the
        # client sent comes back on its response (and names the daemon
        # journey — tests/test_daemon.py pins that leg).
        http_trace_ok = doc0.get("trace_id") == "smoke-trace-http"
        sresp = None
        for _ in range(4):  # reconnect-and-retry across injected drops
            sc = SocketClient(daemon.socket_port)
            try:
                sresp = sc.request({"x": X.tolist(), "key": "sk-gold",
                                    "trace_id": "smoke-trace-sock"})
                break
            except (ConnectionError, OSError):
                continue
            finally:
                sc.close()
        socket_ok = (
            sresp is not None and sresp["status"] == 200
            and np.array_equal(np.asarray(sresp["y"], np.float32), ref1)
        )
        socket_trace_ok = (
            sresp is not None
            and sresp.get("trace_id") == "smoke-trace-sock"
        )
        auth_status = http_post(
            daemon.http_port, "/predict", {"x": X.tolist()}
        )[0]
        be_codes = [
            http_post(daemon.http_port, "/predict", {"x": X.tolist()},
                      {"X-API-Key": "sk-be"})[0]
            for _ in range(6)
        ]
        h_st, h_body = http_get(daemon.http_port, "/healthz")
        health = json.loads(h_body)
        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        # Control plane is token-locked when tenants are configured: a
        # data-plane key must not swap the model.
        swap_denied = http_post(
            daemon.http_port, "/swap", {"artifact": a2}, timeout=120,
            retries=1,
        )[0]
        # retries=1: /swap is NOT idempotent — a retried ack-lost swap
        # would run twice and land one generation past the expectation.
        swap_st, swap_doc = http_post(
            daemon.http_port, "/swap", {"artifact": a2},
            {"X-Swap-Token": "smoke-swap-token"}, timeout=120,
            retries=1,
        )
        # A few post-swap responses, then stop.
        for _ in range(max(4, requests // 4)):
            http_post(daemon.http_port, "/predict", {"x": X.tolist()},
                      {"X-API-Key": "sk-gold"})
        stop.set()
        t.join(timeout=60)
        h2_st, h2_body = http_get(daemon.http_port, "/healthz")
        health2 = json.loads(h2_body)
        gen_attribution_ok = True
        for gen, y in traffic_results:
            expect = ref1 if gen == 0 else ref2
            if not np.array_equal(y, expect):
                gen_attribution_ok = False
        gens = sorted({g for g, _ in traffic_results})
        stats = daemon.stats()
        result = {
            "metric": "serve_daemon_smoke",
            "http_port": daemon.http_port,
            "socket_port": daemon.socket_port,
            "fingerprints": [art1.fingerprint, art2.fingerprint],
            "traffic_responses": len(traffic_results),
            "traffic_errors": traffic_errors[:10],
            "generations_seen": gens,
            "be_codes": be_codes,
            "pass": {
                "http_bit_identical": bool(http_ok),
                "socket_bit_identical": bool(socket_ok),
                "trace_id_http_echo": bool(http_trace_ok),
                "trace_id_socket_echo": bool(socket_trace_ok),
                "auth_403": auth_status == 403,
                "quota_429": 429 in be_codes,
                "swap_tokenless_403": swap_denied == 403,
                "healthz_identity": (
                    h_st == 200
                    and health.get("generation") == 0
                    and health.get("artifact_fingerprint")
                    == art1.fingerprint
                    and health.get("draining") is False
                ),
                "swap_200": swap_st == 200
                and swap_doc.get("generation") == 1,
                "healthz_post_swap": h2_st == 200
                and health2.get("generation") == 1
                and health2.get("artifact_fingerprint") == art2.fingerprint,
                "zero_dropped_under_swap": not traffic_errors,
                "generation_attribution": gen_attribution_ok
                and len(gens) >= 1,
                "zero_active_leftover": stats["active_requests"] == 0,
            },
        }
        result["ok"] = all(result["pass"].values())
        return result
    finally:
        daemon.close()


def main(argv=None) -> int:
    from keystone_tpu.config import config

    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact", help="model artifact path (save_artifact)")
    ap.add_argument("--host", default=None,
                    help="bind address for both ingresses (default "
                         "KEYSTONE_SERVE_HOST = 127.0.0.1; 0.0.0.0 to "
                         "serve external traffic)")
    ap.add_argument("--port", type=int, default=None,
                    help="HTTP ingress port (default KEYSTONE_SERVE_PORT; "
                         "0 = ephemeral)")
    ap.add_argument("--socket-port", type=int, default=None,
                    help="framed-socket ingress port "
                         "(default KEYSTONE_SERVE_SOCKET_PORT)")
    ap.add_argument("--devices", type=int, default=None,
                    help="replica pool width (default "
                         "KEYSTONE_SERVE_DEVICES)")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="run the live end-to-end smoke and exit 0/1")
    args = ap.parse_args(argv)

    # Warmup compiles rungs x replicas before the first request; a restart
    # against the same artifact loads them from the compile cache.
    from keystone_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()

    if args.smoke:
        result = run_smoke()
        print(json.dumps(result))
        if result["ok"]:
            print("serve-daemon smoke: PASS", file=sys.stderr)
        else:
            failed = [k for k, v in result["pass"].items() if not v]
            print(f"serve-daemon smoke: FAIL {failed}", file=sys.stderr)
        return 0 if result["ok"] else 1

    if not args.artifact:
        print("--artifact is required (or use --smoke)", file=sys.stderr)
        return 2

    from keystone_tpu.workflow.daemon import ServingDaemon

    daemon = ServingDaemon(
        artifact=args.artifact,
        host=args.host,
        http_port=args.port,
        socket_port=args.socket_port,
        devices=args.devices,
        max_batch=args.max_batch,
    )
    tenant_mode = (
        "open (no tenants)" if not config.tenants
        else f"{len(config.tenants.split(','))} tenant(s)"
    )
    print(
        f"serving generation {daemon.generation} "
        f"(artifact {daemon.artifact_fingerprint[:12]}) on "
        f"http://{daemon.host}:{daemon.http_port} + "
        f"socket {daemon.host}:{daemon.socket_port} — {tenant_mode}; "
        "POST /swap to hot-swap; Ctrl-C to stop",
        file=sys.stderr,
    )
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        daemon.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
