import importlib.util
import os
import signal
import warnings

# The suite never writes JAX's persistent compilation cache: entry points
# the tests drive (procs-mode children included, which inherit this
# environment) enable it, and this keeps it off for all of them.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax
import pytest

jax.config.update("jax_enable_compilation_cache", False)

warnings.filterwarnings("ignore", category=DeprecationWarning)

# pytest-timeout provides the real per-test cap (pyproject.toml sets the
# default; @pytest.mark.timeout overrides per test). The container image
# may not ship the plugin, so a SIGALRM fallback below enforces the same
# budget — coarser (whole-second, main-thread only), but a hung
# subprocess test still fails instead of wedging the whole run.
_HAVE_TIMEOUT_PLUGIN = importlib.util.find_spec("pytest_timeout") is not None
_FALLBACK_DEFAULT_S = 600


def pytest_addoption(parser):
    if not _HAVE_TIMEOUT_PLUGIN:
        # claim the ini keys the plugin would own, so pyproject's
        # `timeout =` neither warns nor goes unenforced
        parser.addini("timeout", "per-test timeout in seconds "
                                 "(fallback implementation)")
        parser.addini("timeout_method", "ignored by the fallback "
                                        "(always SIGALRM)")


def _timeout_limit(item) -> float:
    marker = item.get_closest_marker("timeout")
    if marker is not None and marker.args:
        return float(marker.args[0])
    try:
        return float(item.config.getini("timeout") or _FALLBACK_DEFAULT_S)
    except (ValueError, TypeError):
        return _FALLBACK_DEFAULT_S


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if _HAVE_TIMEOUT_PLUGIN or not hasattr(signal, "SIGALRM"):
        yield
        return
    limit = _timeout_limit(item)

    def _alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {limit:.0f}s per-test cap "
            "(conftest SIGALRM fallback; install pytest-timeout for the "
            "full implementation)")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(max(int(limit), 1))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def mesh1():
    """Trivial 1x1 mesh — the single-device path of the manual-TP code."""
    return jax.make_mesh((1, 1), ("data", "model"))
