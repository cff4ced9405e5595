"""Test configuration.

Device-plane tests run on a virtual 8-device CPU mesh so the suite needs no
TPU hardware (SURVEY.md section 4: "Add what the reference lacks: a CPU
fake-mesh backend so tests run without TPUs").  The env vars must be set
before jax is first imported anywhere in the process.
"""

import asyncio
import inspect
import os

# Every test runs on the virtual CPU mesh, whatever the machine holds: the
# chip belongs to chip_smoke.py (tests/test_onchip.py starts it as a child
# with a clean environment).  Set before jax is first imported.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"


def free_port() -> int:
    """An OS-assigned free TCP port (bind :0, read it back, release).

    Tests previously drew random.randint(10000, 50000), which collides
    when several pytest processes run concurrently on one host (observed:
    OSError address-in-use flakes).  The tiny bind-then-close TOCTOU
    window is far narrower than a 40000-value birthday problem."""
    import socket

    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


import pytest  # noqa: E402  (after the jax platform pinning above)


@pytest.fixture
def port() -> int:
    """Shared across every socket-using suite; see free_port()."""
    return free_port()


@pytest.fixture
def port2() -> int:
    """A second independent listener port (two-pair tests)."""
    return free_port()


# Minimal asyncio test support (pytest-asyncio is not available in the image):
# coroutine test functions run under asyncio.run, mirroring the reference's
# module-wide `pytestmark = pytest.mark.asyncio` setup.


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run coroutine test in an asyncio event loop")


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {n: pyfuncitem.funcargs[n] for n in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True


def rolling_primitive_oracle(params, cfg):
    """Single-request greedy oracle over the SAME primitives rolling
    SlotServer admission uses (prefill_rolling chunks + rolling
    decode_step + greedy sample) — the bit-exact reference the rolling
    continuous-batching tests pin against (fp, int8-KV, and W8 variants
    all share this one loop)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from starway_tpu.models.generate import _sample, decode_step
    from starway_tpu.models.llama import rope_tables
    from starway_tpu.models.serving import _rolling_prefill_state

    def oracle(prompt, max_new, horizon):
        logits, cache = _rolling_prefill_state(
            params, cfg, np.asarray(prompt, np.int32))
        rope = rope_tables(horizon, cfg.head_dim, cfg.rope_theta)
        toks = [int(_sample(logits, jax.random.PRNGKey(0), 0.0, None,
                            None)[0])]
        pos = len(prompt)
        while len(toks) < max_new:
            logits, cache = decode_step(
                params, cache, jnp.asarray([toks[-1]], jnp.int32),
                jnp.asarray([pos], jnp.int32), cfg, rope, rolling=True)
            toks.append(int(_sample(logits, jax.random.PRNGKey(0), 0.0,
                                    None, None)[0]))
            pos += 1
        return np.asarray(toks, np.int32)

    return oracle


def sorts_over(text: str, width: int) -> list:
    """The ``sort`` instructions of a compiled program's text whose
    operands' last dimension is ``width`` (a nucleus found by sorting its
    vocabulary would be one: tests/test_mtp_serving.py, test_aot_tpu.py)."""
    return [line.strip()[:160] for line in text.splitlines()
            if " sort(" in line
            and f",{width}]" in line.split(" sort(")[0].replace("[", ",")]


@pytest.fixture
def force_kernels(monkeypatch):
    """``force_kernels(on)``: substitute the ONE decision of
    ``starway_tpu.ops`` (``dispatch.use_kernels``) until the test ends.
    ``True`` makes every operation traced from then on take its Pallas
    kernel (interpreted on the CPU), ``False`` its lax twin.  For the few
    tests that need a whole PROGRAM on one side; a test of one side calls
    the kernel (``interpret=True``) or the ``*_lax`` twin by name."""
    def force(on: bool) -> None:
        monkeypatch.setattr("starway_tpu.ops.dispatch.use_kernels",
                            lambda: on)

    return force
