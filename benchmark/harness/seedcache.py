"""A cache for what is made from the seed alone (vocabulary, JPEGs, record
shards): ``benchmark/.cache/<seed>-<hash of what made it>/<name>``, rebuilt on
a miss. The hash covers the source files, the benchmark's and the program's,
and the parameters that shape the data, so an edit to a generator or to the
program's writer never reads a stale cache. A miss is paid in ``setup_s``;
the run prints which it was (``seed_cache_hit`` in ``# setup_split_s``).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Callable

ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    ".cache")
#: ``<seed>-<hash>`` directories kept (a seed's images weigh 100 MB, its
#: record shards 270 MB); a full check of a cell uses fewer seeds than this
KEEP = 16


def key(seed: int, files: list[str], params: dict) -> str:
    h = hashlib.blake2b(digest_size=8)
    for path in sorted(files):
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(json.dumps(params, sort_keys=True).encode())
    return f"{int(seed)}-{h.hexdigest()}"


def ensure(cache_key: str, name: str,
           build: Callable[[str], None]) -> tuple[str, bool]:
    """``(directory, hit)``. ``build(tmp_dir)`` fills a scratch directory
    that is renamed into place only when it returns, so a killed build never
    leaves a half-made cache behind."""
    final = os.path.join(ROOT, cache_key, name)
    if os.path.isfile(os.path.join(final, ".complete")):
        return final, True
    tmp = final + ".building"
    shutil.rmtree(tmp, ignore_errors=True)
    shutil.rmtree(final, ignore_errors=True)
    os.makedirs(tmp)
    build(tmp)
    with open(os.path.join(tmp, ".complete"), "w") as f:
        f.write("ok\n")
    os.rename(tmp, final)
    others = [os.path.join(ROOT, d) for d in os.listdir(ROOT) if d != cache_key]
    for old in sorted(others, key=os.path.getmtime, reverse=True)[KEEP - 1:]:
        shutil.rmtree(old, ignore_errors=True)
    return final, False
