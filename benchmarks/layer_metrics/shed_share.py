"""Query wire and admission: requests the program refused (``T_SHED``
from the element's slot admission) over requests attempted, in percent."""


def read(run):
    if not run.requests:
        return None
    return 100.0 * run.counters["shed"] / len(run.requests)
