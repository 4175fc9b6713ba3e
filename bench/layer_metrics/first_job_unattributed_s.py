"""Job: seconds of the first job (creating the `Context` to the end of the
first `collect()`) that no span names: less `context:init`, `ingest:sniff`
and the direct children of its `job` span, all on the job's thread."""

from layer_metrics.unattributed_share import named_seconds


def read(run: dict):
    first = run["first_job"]
    named = named_seconds(first["spans"])
    if named is None:
        return None
    return max(first["seconds"] - named, 0.0)
