__all__ = ["ArrayLoader", "DevicePrefetcher", "HostStagingPool"]

_PIPELINE = {"DevicePrefetcher", "HostStagingPool"}


def __getattr__(name):
    # lazy re-export (PEP 562): loader/pipeline import jax, and data-pipeline
    # worker processes (spawn/forkserver) import submodules of this package —
    # they must not pay a full JAX import + RSS each just to reach the
    # numpy-only decode/transform code
    if name in _PIPELINE:
        from deep_vision_tpu.data import pipeline

        return getattr(pipeline, name)
    if name in __all__:
        from deep_vision_tpu.data import loader

        return getattr(loader, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
