"""90th percentile, over every request due in the window, of the time
from its due time to its first token (a request with none by the drain
limit counts at the drain's end). It swings with which requests queue
behind which admissions, so it is reported per layer, beside the
end-to-end tail of the inter-token gaps."""


def read(run):
    return (run.counters.get("serving") or {}).get("ttft_p90_ms")
