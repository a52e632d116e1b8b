"""Seeded input data, made once per (generator, parameters, seed).

A traffic file names a generator (``generators/<name>.py``, with
``generate(out_dir, seed, **params)`` returning the product's data
origin) and its parameters.  The same seed gives the same bytes; another
seed gives other bytes of the same sizes, so the work of a run does not
depend on the seed.  Data live under the work directory keyed by
generator, parameters and seed: a second run with the same seed finds
them, one with another seed regenerates in seconds.
"""

import hashlib
import json
import os
import shutil

from benchmark.lib import manifest

DONE = "_complete"


def ensure(data_root, generator, params, seed, bench_dir=manifest.BENCH_DIR):
    """Generate (or find) the data; returns the product's data origin."""
    generate = manifest.load_named("generators", generator,
                                   bench_dir).generate
    key = hashlib.sha256(json.dumps(
        [generator, params], sort_keys=True).encode()).hexdigest()[:12]
    out_dir = os.path.join(data_root, "%s-%s-%d" % (generator, key, seed))
    marker = os.path.join(out_dir, DONE)
    if os.path.exists(marker):
        with open(marker) as fh:
            return fh.read().strip()
    # Data of other seeds are dead weight: drop them.
    if os.path.isdir(data_root):
        for name in os.listdir(data_root):
            if name.startswith("%s-%s-" % (generator, key)):
                shutil.rmtree(os.path.join(data_root, name),
                              ignore_errors=True)
    os.makedirs(out_dir)
    origin = generate(out_dir, seed, **params)
    with open(marker, "w") as fh:
        fh.write(origin)
    return origin
