"""Of the mesh local stages the program built in a traced run's counted
calls, the share (%) that take the count and every chunk leg from one
kernel pass (telemetry counters ``mesh.local_onepass`` and
``mesh.local_perleg``). Nothing where no local stage was built."""


def read(run):
    onepass = sum(run.counters.get("mesh.local_onepass", []))
    perleg = sum(run.counters.get("mesh.local_perleg", []))
    return 100.0 * onepass / (onepass + perleg) if onepass + perleg else None
