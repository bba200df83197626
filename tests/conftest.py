"""Test configuration: run everything on XLA-CPU with 8 virtual devices so
multi-chip sharding tests execute without TPU hardware (SURVEY §4 TPU
equivalent: `XLA_FLAGS=--xla_force_host_platform_device_count=8`)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield


# Per-test wall-clock timeout (reference: the test scheduler's per-UT
# timeout, unittests/CMakeLists.txt set_tests_properties TIMEOUT). No
# pytest-timeout in this image, so a SIGALRM guard: default 300 s, override
# with @pytest.mark.timeout_s(N).
@pytest.fixture()
def reference_paddle():
    """Path of the reference's ``python/paddle`` tree, which the export
    parity tests read; they skip on a machine that does not have it."""
    path = "/root/reference/python/paddle"
    if not os.path.isdir(path):
        pytest.skip(f"the reference tree {path} is not on this machine")
    return path


import signal  # noqa: E402


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    marker = request.node.get_closest_marker("timeout_s")
    limit = int(marker.args[0]) if marker else 300

    def _alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded {limit}s wall-clock (per-test timeout guard)")
    if hasattr(signal, "SIGALRM"):
        old = signal.signal(signal.SIGALRM, _alarm)
        signal.alarm(limit)
        yield
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
    else:  # pragma: no cover
        yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout_s(n): per-test wall-clock limit in seconds")
