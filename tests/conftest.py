"""Shared fixtures.  NOTE: XLA_FLAGS is deliberately NOT set here — smoke
tests and benchmarks must see the single real CPU device; multi-device
integration tests run in subprocesses with their own flags."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_integration(script: str, *args: str, devices: int = 8,
                    timeout: int = 900) -> str:
    """Run an integration script in a fresh process with N host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    path = os.path.join(REPO, "tests", "integration", script)
    proc = subprocess.run([sys.executable, path, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{script} failed:\nSTDOUT:\n{proc.stdout[-4000:]}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


def junk_past_length(bt, lengths, page, nan_frame):
    """The block table with every entry at or past its row's length
    replaced by the id of a NaN-filled frame or an id out of range: a
    paged kernel that reads a page past a length returns NaN there."""
    import numpy as np
    import jax.numpy as jnp
    MB = bt.shape[1]
    past = np.arange(MB)[None, :] * page >= np.asarray(lengths)[:, None]
    junk = np.choose(np.arange(MB) % 3, [nan_frame, nan_frame + 5, -3])
    return jnp.asarray(np.where(past, junk[None, :], np.asarray(bt)),
                       jnp.int32)


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)
