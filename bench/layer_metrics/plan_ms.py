"""Entry and plan: the harness's clock around one `plan_stages` call on
the cell's pipeline (sampling, type inference, the analyzer, the split
tuner), in milliseconds."""


def read(run: dict):
    return run["plan_ms"]
