"""Host time of the input path per round: the mean of the benchmark's
``chipbench.input`` spans (around ``AdmmSetup.batch``) in the trace."""


def read(rec):
    spans = rec["trace"]["host_spans"].get("chipbench.input")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
