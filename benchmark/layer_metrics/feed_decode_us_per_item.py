"""Thread-seconds the feed's threads spent decoding JPEGs (span
``dls.feed/decode``, opened by ``vision.decode_jpeg`` around the decode
alone), per item: sum of ``input_decode_s`` over the items of the same laps.
Where ``map_parallel``'s pool decodes it is a part of
``feed_map_us_per_item``'s time, and like it summed over the pool's threads.
A feed that decodes nothing, and a program without the span, has no such key
and reads nothing."""


def read(ctx):
    laps = [e for e in ctx["laps"] if "input_decode_s" in e]
    steps = sum(e["steps"] for e in laps)
    if not steps:
        return None
    return (1e6 * sum(e["input_decode_s"] for e in laps)
            / (steps * ctx["items_per_step"]))
