"""Host seconds around the first ``run`` of the step program: trace,
compile or load from the cache, and the step itself."""


def value(run):
    return run["times"]["first_call_s"]
