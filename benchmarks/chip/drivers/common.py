"""What the drivers share: the program's view of a configuration, tracing."""

from __future__ import annotations

import contextlib


def program_config(config: dict):
    """The `repro.interface.InterfaceConfig` a configuration file states.

    The file sets no ``impl``: a cell measures what a user gets with the
    program's defaults.
    """
    from repro.core.cam import CamConfig
    from repro.interface import InterfaceConfig
    from repro.noc.topology import NocConfig

    fab = config["fabric"]
    cam = fab["cam"]
    cfg = InterfaceConfig(
        chips=fab["chips"], cores_per_chip=fab["cores_per_chip"],
        neurons_per_core=fab["neurons_per_core"], scheme=fab["scheme"],
        cam=CamConfig(entries=fab["cam_entries_per_core"], bits=cam["bits"],
                      sense_bits=cam["sense_bits"], cscd=cam["cscd"],
                      feedback=cam["feedback"],
                      speculative=cam["speculative"]),
        noc=NocConfig(scheme=fab["noc"]))
    if cfg.cores != fab["cores"]:
        raise ValueError(f"{config['name']}: {fab['chips']} chips x "
                         f"{fab['cores_per_chip']} cores is not "
                         f"cores={fab['cores']}")
    return cfg


def interface_params(arrays):
    """`repro.interface.InterfaceParams` of ``(tags, valid, weights,
    targets)`` device arrays."""
    from repro.interface.types import InterfaceParams

    return InterfaceParams(*arrays)


@contextlib.contextmanager
def traced(trace_dir: str | None):
    """Profile the body into ``trace_dir`` as the ``bench.window`` span.

    The program's own spans (`repro.obs.trace`) are recorded only while a
    tracer is active, so one is active here too.  With no ``trace_dir``
    nothing is traced and the program's spans stay off.
    """
    if trace_dir is None:
        yield
        return
    import jax

    from repro.obs import trace as obs_trace

    jax.profiler.start_trace(trace_dir)
    try:
        with obs_trace.Tracer(), jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
