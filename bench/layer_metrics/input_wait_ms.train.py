"""Host time per training step spent building its input: the benchmark's
span ``bench.train.input`` around ``ImagePipeline.batch_at``, averaged over
the window's steps (host clock).  Moves ``train_samples_per_s``."""
import statistics


def read(ctx):
    builds = ctx.counters.get("input_s")
    return 1e3 * statistics.mean(builds) if builds else None
