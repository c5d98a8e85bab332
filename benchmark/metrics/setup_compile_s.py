"""Union of every compile interval of the launch record before the window
(trace, lowering, backend compile, the persistent cache's read), whoever
jitted: the program's, the driver's weight draws, the parity probes."""

from benchmark import launch


def read(run: dict):
    return launch.number(run, "setup_compile_s")
