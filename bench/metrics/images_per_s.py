"""Images completed over the elapsed window: the window ends at the first
batch completion after the run's seconds."""


def read(run):
    done = run.window_batches()
    if not done or run.window_close <= 0:
        return None
    return sum(b.n for b in done) / run.window_close
