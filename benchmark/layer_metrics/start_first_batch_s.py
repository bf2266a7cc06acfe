"""The loop's wait for the feed in the first lap: the feed's threads start,
the first batches are assembled and put on the devices. ``first_batch_s`` of
the program's ``startup`` record, which is that lap's ``input_wait_s`` +
``input_put_s``."""

from benchmark.harness import startup


def read(ctx):
    return startup.seconds(ctx, "first_batch_s")
