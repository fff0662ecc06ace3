"""Device idle time inside the harness's ``generate_bucketed`` spans, per
batch, over the traced batches (device trace). The reader of every
``executor_idle_ms_per_batch.<part>``, whose part names the end-to-end
metric it moves (``.backlog``: ``images_per_s``)."""


def read(run):
    spans = run.traced_batches()
    if not spans:
        return None
    return 1e3 * sum(s["seconds"] - s["busy_s"] for s in spans) / len(spans)
