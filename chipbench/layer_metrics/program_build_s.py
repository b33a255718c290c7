"""Host seconds around model build + ``minimize`` (both programs)."""


def value(run):
    return run["times"]["build_s"]
