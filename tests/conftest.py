"""Test harness: the CPU backend with a forced 8-device virtual mesh.

The suite runs on the CPU; sharding correctness is tested on XLA's
forced host-platform device count, exactly as the driver's
dryrun_multichip does. The chip is reached only through chip_smoke.py;
tests/test_tpu_compile.py compiles for a described (not attached) v5e.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache: the suite's dominant cost is XLA
# recompiling near-identical engine programs in every test process.
# Cache entries are keyed on HLO hash, so identical (shape,
# handler-table) engines across tests and across runs share one compile.
# A set JAX_COMPILATION_CACHE_DIR wins (JAX reads it itself, and the CLI
# children some tests start inherit it); otherwise the suite keeps its
# own .jax_cache_cpu, apart from the entry points' .jax_cache.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                 ".jax_cache_cpu"),
)
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pytest_configure(config):
    """Suite-wide hang diagnosis: any single test stuck past this limit
    gets every thread's stack dumped by pytest's faulthandler plugin —
    the same diagnosis the runtime watchdog gives production runs. Set
    just under CI's 870s outer `timeout -k` so the dump happens while
    the process is still alive to print it. The raw inicfg dict is read
    lazily per test (and getini would cache a premature default), so
    only set it when pyproject didn't."""
    if "faulthandler_timeout" not in config.inicfg:
        config.inicfg["faulthandler_timeout"] = "840"


def pytest_collection_modifyitems(config, items):
    """Auto-mark tests so a smoke lane exists: `pytest -m "not slow"`
    skips the heavyweight end-to-end runs. Measured warm-cache on a
    single-core box: smoke ~7 min, full ~25 min (sims execute on XLA's
    CPU backend; compiles hit .jax_cache after the first run)."""
    import pytest

    slow_files = {
        "test_tor_bitcoin.py", "test_multimodel.py", "test_tcp_matrix.py",
        "test_proc_tier.py", "test_multichip.py", "test_interpose.py",
        "test_proc_scale.py", "test_udp_tier.py", "test_pthreads_tier.py",
        "test_ref_capstones.py",
    }
    for item in items:
        if item.fspath.basename in slow_files:
            item.add_marker(pytest.mark.slow)
        if item.fspath.basename == "test_ref_capstones.py":
            # dedicated lane CI-gating the README's "reference test
            # sources run unmodified" claim: `pytest -m capstone`
            item.add_marker(pytest.mark.capstone)
