"""Test env: 8 virtual CPU devices so pmap/pjit/mesh paths are exercised
without a pod (SURVEY §4 implication (d))."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Tests run on the CPU: the virtual devices above are host devices, and a
# test must never take a chip.  Pinned in code (before any backend init)
# so it also holds when the suite is started without JAX_PLATFORMS=cpu.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from deep_vision_tpu.parallel import make_mesh

    return make_mesh({"data": 8})


@pytest.fixture(scope="session")
def mesh1():
    from deep_vision_tpu.parallel import make_mesh

    return make_mesh({"data": 1}, devices=jax.devices()[:1])


@pytest.fixture(scope="session")
def host_devices():
    """The 8 forced host devices multi-device serving tests replicate
    and shard over (tests/test_replicas.py)."""
    devs = jax.local_devices()
    assert len(devs) >= 8, f"expected 8 forced host devices, got {devs}"
    return devs


def _equations(jaxpr, closed=()):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in closed:
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, closed)


@pytest.fixture(scope="session")
def jaxpr_equations():
    """``walk(jaxpr, closed=())``: every equation of a jaxpr and of the
    jaxprs nested in its equations' parameters (pjit, remat, custom_jvp,
    pallas_call, ...), those of the primitives named in ``closed`` left
    unopened."""
    return _equations


@pytest.fixture
def kept_between_passes(capsys):
    """``kept(loss, *args)``: dtype and shape of what autodiff keeps of
    ``loss`` between the passes, in the order computed, beside its
    arguments, constants and the cosine a test's own ``sin`` keeps."""
    def kept(loss, *args):
        jax.ad_checkpoint.print_saved_residuals(loss, *args)
        found = []
        for line in capsys.readouterr().out.splitlines():
            aval, where = line.split(" ", 1)
            if where.startswith(("from the argument", "from a constant")) \
                    or "output of cos" in where:
                continue
            dtype, shape = aval[:-1].split("[")
            found.append((dtype, tuple(int(n) for n in shape.split(",") if n)))
        return found

    return kept


@pytest.fixture(scope="session")
def images_with_margin():
    """``pick(logits_of, n, margin)``: n uint8 32x32x1 images whose two
    largest reference logits lie at least ``margin`` apart, so a test of
    "top-1 intact within a tolerance" is decided by the tolerance and not
    by a near tie.  Uniform noise gives a randomly initialised LeNet ties
    of 1e-3; block patterns spread its logits.  ``logits_of`` maps a
    (8, 32, 32, 1) uint8 batch to the reference's float32 logits."""
    import numpy as np

    def pick(logits_of, n, margin):
        found = []
        for start in range(0, 256, 8):
            batch = np.stack([
                np.kron(np.random.RandomState(seed).rand(4, 4) > 0.5,
                        np.ones((8, 8))).astype(np.uint8)[..., None] * 255
                for seed in range(start, start + 8)])
            top2 = np.sort(logits_of(batch), axis=1)[:, -2:]
            found += [img for img, (second, first) in zip(batch, top2)
                      if first - second >= margin]
            if len(found) >= n:
                return found[:n]
        raise AssertionError(f"{len(found)} of 256 patterns have a top-2 "
                             f"margin of {margin}; wanted {n}")

    return pick


# The dvtlint runtime half (docs/ANALYSIS.md): every chaos/gateway/replicas
# test runs with DVT_LOCK_SANITIZER semantics on — serve/* locks become
# SanitizedLocks recording acquisition order, and the test FAILS at teardown
# if any thread observed a lock-order inversion (even one a worker thread
# swallowed). Engines/gateways are constructed inside the tests, after this
# fixture enables the seam, so every lock they create is instrumented.
_SANITIZED_MARKERS = {"chaos", "gateway", "replicas", "models", "deploy",
                      "edge", "mesh", "batch"}


@pytest.fixture(autouse=True)
def _dvt_lock_sanitizer(request):
    from deep_vision_tpu.analysis import sanitizer

    if not (_SANITIZED_MARKERS
            & {m.name for m in request.node.iter_markers()}):
        yield
        return
    was = sanitizer.enabled()
    sanitizer.enable(True)
    sanitizer.reset()
    try:
        yield
        violations = sanitizer.violations()
        assert not violations, (
            "lock-order violations during test:\n  " + "\n  ".join(violations))
    finally:
        sanitizer.reset()
        sanitizer.enable(was)
