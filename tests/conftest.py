import jax
import numpy as np
import pytest

# Tests run single-device on CPU (the 512-device dry-run is subprocess-only,
# per the assignment: XLA_FLAGS must NOT be set globally here).
jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (subprocesses, jax compiles)")
    config.addinivalue_line(
        "markers", "wallclock: real-time tests (threads, sleeps, live "
        "clocks) — the deflake CI leg repeats these 20x")


def pytest_addoption(parser):
    # minimal stand-in for pytest-repeat's --count when the plugin is
    # absent; when pytest-repeat IS installed (CI) its own option wins
    # and this registration raises ValueError — ignore it.
    try:
        parser.addoption("--count", action="store", default=1, type=int,
                         help="run each test N times (pytest-repeat "
                              "fallback)")
    except ValueError:
        pass


def pytest_generate_tests(metafunc):
    count = int(metafunc.config.getoption("--count", 1) or 1)
    if count > 1 and "__repeat__" not in metafunc.fixturenames \
            and not metafunc.config.pluginmanager.hasplugin("pytest_repeat"):
        metafunc.fixturenames.append("__repeat__")
        metafunc.parametrize("__repeat__", range(count),
                             ids=[f"rep{i}" for i in range(count)])

# hypothesis is an optional dependency: when absent, install a stub so the
# property-test modules still *collect* — @given tests turn into skips and
# every plain test in those modules keeps running.
try:
    import hypothesis  # noqa: F401
except ImportError:
    import sys
    import types

    def _given(*_a, **_k):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def _settings(*_a, **_k):
        return lambda fn: fn

    class _Strategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _Strategies()
    sys.modules["hypothesis"] = _hyp


def wait_until(predicate, timeout=10.0, interval=0.005, desc="condition"):
    """Bounded polling for wall-clock tests: spin on ``predicate`` until it
    returns truthy or ``timeout`` elapses (then fail loudly).  Replaces
    bare ``time.sleep(...)`` synchronization, which is the classic flake:
    too short on a loaded CI box, dead time everywhere else."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = predicate()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError(f"timed out after {timeout}s waiting for {desc}")


def oracle_tables(n, L=3, seed=0):
    """Synthetic per-sample oracle tables ``(conf, correct)``, each
    ``(n, L)``: confidence rises with depth in [0.3, 1.0), and a stage's
    answer is correct with probability equal to its confidence."""
    rng = np.random.default_rng(seed)
    conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
    correct = rng.uniform(size=(n, L)) < conf
    return conf, correct.astype(bool)


def serve_closed_loop(spec, policy, workload, conf, correct, **resources):
    """Run ``spec`` through ``Service`` on the §IV closed-loop
    ``workload``, with ``policy`` (an instance, so the registry is
    skipped) deciding over the oracle tables."""
    from repro.serving import Service
    return Service.from_spec(spec, policy=policy, workload=workload,
                             conf_table=conf, correct_table=correct,
                             **resources).run()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def make_inputs(cfg, key, batch, seq):
    """Shape-correct smoke inputs for any modality."""
    if cfg.modality == "features":
        from repro.models.model import FEATURE_DIM
        return {"features": jax.random.normal(key, (batch, seq, FEATURE_DIM))}
    if cfg.modality == "vision_stub":
        n_text = max(1, seq - cfg.num_patches)
        return {
            "tokens": jax.random.randint(key, (batch, n_text), 0, cfg.vocab_size),
            "patch_embeds": jax.random.normal(
                key, (batch, cfg.num_patches, cfg.d_model)),
        }
    if cfg.modality == "audio_stub":
        return {"tokens": jax.random.randint(
            key, (batch, cfg.num_codebooks, seq), 0, cfg.vocab_size)}
    return {"tokens": jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)}
