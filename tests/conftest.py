"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; sharding tests run on a virtual
8-device CPU backend (the analog of the reference's in-process multi-node
harness, test/pilosa.go:297-352 MustRunCluster).

Tier-1 runs with JAX_PLATFORMS=cpu exported (the ROADMAP command) and JAX
honours it; force_platform (pilosa_tpu.parallel.mesh) pins the same choice
in jax.config and adds the 8 virtual host devices, before any backend use
(conftest imports run first). The persistent compile cache stays OFF for
the in-process suite: a tier-1 verdict must not depend on what an earlier
run left in <repo>/.jax_cache (server subprocesses still use it — that is
the production path).
"""

import os

# runtime lock-order witness ON for the whole suite (export
# PILOSA_TPU_LOCKCHECK=0 to opt out): every concurrency test doubles as
# a race regression test — the autouse guard below fails the test that
# first forms a lock-order cycle or holds a lock across RPC/dispatch.
# Armed by direct install() rather than by exporting the env var: the
# subprocess clusters (clusterproc/chaos tests) would inherit the env
# and pay witness overhead whose reports nothing ever reads — pure load
# that erodes the SWIM-clock margins of the liveness tests. Installed
# before the first pilosa_tpu.parallel import so every lock the package
# constructs afterwards is wrapped.
from pilosa_tpu.analysis import lockwitness

if os.environ.get(lockwitness.ENV_GATE, "") != "0":
    lockwitness.install()

from pilosa_tpu.parallel.mesh import force_platform

force_platform("cpu", host_devices=8)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


def pytest_sessionstart(session):
    assert jax.devices()[0].platform == "cpu", jax.devices()
    assert len(jax.devices()) == 8, jax.devices()


@pytest.fixture(autouse=True)
def _lockwitness_guard():
    """With the witness active, any lock-order cycle or held-across-
    RPC/dispatch violation fails the test that formed it, with the
    offending stacks."""
    if not lockwitness.ACTIVE:
        yield
        return
    before = lockwitness.violation_count()
    yield
    after = lockwitness.violation_count()
    assert after == before, (
        "lock-order witness recorded new violations during this test:\n"
        + lockwitness.format_violations())


def pytest_sessionfinish(session, exitstatus):
    if lockwitness.ACTIVE:
        rep = lockwitness.report()
        print(f"\nlockwitness: {rep['edges']} lock-order edges, "
              f"{len(rep['cycles'])} cycles, "
              f"{len(rep['heldAcrossBlocking'])} held-across-blocking")


@pytest.fixture(autouse=True)
def _failpoint_isolation():
    """Failpoint state is process-global (utils/failpoints.py): reset it
    around every test so a leaked activation can never bleed into an
    unrelated test's I/O paths."""
    from pilosa_tpu.utils import failpoints

    failpoints.reset()
    yield
    failpoints.reset()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On a chaos-marked test failure, print the chaos seed and the exact
    fired-failpoint schedule — the replay recipe (re-arm the same seed, or
    re-fire the logged schedule via explicit configure() calls)."""
    out = yield
    rep = out.get_result()
    if rep.when == "call" and rep.failed \
            and item.get_closest_marker("chaos") is not None:
        from pilosa_tpu.utils import failpoints

        rep.sections.append((
            "chaos replay",
            "deterministic replay recipe (seed + fired schedule):\n"
            + failpoints.describe()))
