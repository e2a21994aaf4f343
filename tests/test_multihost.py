"""Multi-host execution: two real processes rendezvous over
``jax.distributed.initialize`` (the DCN control-plane seam,
``utils/distributed.py``) and run one psum'd normal-equations solve across
a mesh spanning both — proving the distributed backend executes, not just
imports. Ref: SURVEY.md §5 distributed-backend row; the reference's
local[n]-vs-cluster equivalence argument [unverified].
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_WORKER = textwrap.dedent(
    """
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    # The production seam: env knobs -> rendezvous (utils/distributed.py).
    from keystone_tpu.utils.platform import setup_platform
    setup_platform()

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.local_devices()) == 2
    assert len(jax.devices()) == 4  # the mesh spans both processes

    from keystone_tpu.linalg import RowMatrix, solve_least_squares_normal

    rng = np.random.default_rng(0)  # same bytes on every host
    X = rng.normal(size=(64, 8)).astype(np.float32)
    W_true = rng.normal(size=(8, 3)).astype(np.float32)
    Y = X @ W_true
    A = RowMatrix.from_array(X)
    B = RowMatrix.from_array(Y)
    W = np.asarray(solve_least_squares_normal(A, B, lam=0.0))
    err = np.linalg.norm(W - W_true) / np.linalg.norm(W_true)
    assert err < 1e-4, err
    print(f"MULTIHOST_OK process={jax.process_index()} err={err}", flush=True)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_psum_solve(tmp_path):
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for pid in (0, 1):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["KEYSTONE_COORDINATOR"] = f"127.0.0.1:{port}"
        env["KEYSTONE_NUM_PROCESSES"] = "2"
        env["KEYSTONE_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _WORKER],
                env=env,
                cwd=repo,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"rc={rc}\nstdout:{out[-1000:]}\nstderr:{err[-2000:]}"
        assert "MULTIHOST_OK" in out
