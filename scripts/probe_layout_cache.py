"""Does a pinned device layout survive the persistent compile cache?

    chiprun -- sh -c 'export JAX_COMPILATION_CACHE_DIR=/tmp/probe_cc; \
        python scripts/probe_layout_cache.py; python scripts/probe_layout_cache.py'

Run twice on one cache directory: the first process compiles (a miss),
the second finds every program in the cache (a hit). Each prints, for a
[2090001, 64] float32 table (the Reddit store's shape): the device's own
layout, the layout after a re-lay into ``Format(rows-major)`` through
the cache and with the cache bypassed (``parallel.compiles_keep_layouts``),
and the layout and values after three donated gather + scatter-add steps
whose input and output are pinned, through the cache and bypassed.

On jaxlib 0.9.0 / TPU v5e (my chip runs, PR 31) the hit loses the pinned
RESULT layouts: the re-lay hands back a table that says it is
column-major, the step's output says so too and its second call is
refused ("Layout passed to jit does not match the layout on the
respective arg"). The buffer itself seems to keep the pinned layout and
only its label to be the default's: an eager gather from such a table
fails in the runtime with "expected parameter 0 of size 535068672 ...
{0,1} but got buffer with incompatible size 1070084096 ... {1,0}". A pin
on a parameter alone survives (the eager gather from a properly
labelled rows-major table reads right values on the hit). Bypassed, all
keep rows-major and the values are right. ``compiles_keep_layouts``
exists for that reason; when this probe reads rows-major on the hit too,
it can go (PERF.md section 7).
"""

import contextlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.layout import Format, Layout  # noqa: E402

from euler_tpu.parallel import (  # noqa: E402
    compiles_keep_layouts,
    enable_compile_cache,
    make_mesh,
    replicated_sharding,
)


def layout_of(a):
    return tuple(a.format.layout.major_to_minor), a.format.layout.tiling


def main() -> int:
    print("compile cache:", enable_compile_cache())
    rep = replicated_sharding(make_mesh(1))
    pinned = Format(Layout((0, 1)), rep)
    host = np.asarray(
        jax.random.uniform(jax.random.PRNGKey(0), (2090001, 64)))
    rows = np.arange(4000, dtype=np.int32) * 7
    want = host[rows] * 27     # three times (row + 2 * row)
    placed = jax.device_put(host, rep)
    print("the device's own layout:", layout_of(placed))

    # two programs of their own names: one process keeps a loaded
    # executable in memory, so a re-lay that came from the cache once
    # would come from there again, bypass or not
    def relay_bypassed(a):
        return a

    def relay_cached(a):
        return a

    with compiles_keep_layouts(pinned):
        relaid = jax.jit(relay_bypassed, out_shardings=pinned)(placed)
    # values are read back by a transfer: a program over the table would
    # itself come from the cache
    print("re-lay, cache bypassed:", layout_of(relaid), "values kept:",
          bool(np.array_equal(np.asarray(relaid), host)))
    print("re-lay through the cache:",
          layout_of(jax.jit(relay_cached, out_shardings=pinned)(placed)))
    try:
        got = np.asarray(relaid[rows])
        print("eager gather from the rows-major table, through the cache:",
              "values right:", bool(np.array_equal(got, host[rows])))
    except jax.errors.JaxRuntimeError as e:
        print("eager gather from the rows-major table, through the cache: "
              "REFUSED: " + str(e).replace("\n", " ")[:300])

    def step(table, ids):
        read = table[ids]
        return table.at[ids].add(read * 2), read.sum()

    ids = jax.device_put(rows, rep)
    for name, bypass in (("through the cache", False),
                         ("cache bypassed", True)):
        def named(table, ids):
            return step(table, ids)

        named.__name__ = "step_" + name.replace(" ", "_")
        fn = jax.jit(named, in_shardings=(pinned, rep),
                     out_shardings=(pinned, rep), donate_argnums=(0,))
        with compiles_keep_layouts(pinned):
            table = jax.jit(relay_bypassed, out_shardings=pinned)(
                jax.device_put(host, rep))
        t0 = time.time()
        try:
            with (compiles_keep_layouts(pinned) if bypass
                  else contextlib.nullcontext()):
                for _ in range(3):
                    table, _ = fn(table, ids)
            ok = bool(np.allclose(np.asarray(table)[rows], want, rtol=1e-6))
            print(f"donated step {name}: {layout_of(table)} values right: "
                  f"{ok} ({time.time() - t0:.2f} s)")
        except (ValueError, jax.errors.JaxRuntimeError) as e:
            print(f"donated step {name}: REFUSED: "
                  + str(e).replace("\n", " ")[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main())
