"""Sandbox rehearsal: what a cell's first run on the chip will compile, and
how long each program takes the TPU's compiler, without the chip.

    JAX_PLATFORMS=cpu python3 -m tpubench.aot_rehearsal --workload <cell> [--rows N]

It runs the cell's warm-up here on the CPU with `jax.jit` wrapped, so that
every program the engine jits is noted with the shapes it was called at;
then it lowers each one for a described `v5e:2x2` chip and compiles it with
the XLA:TPU compiler installed in the sandbox, timing each.  A compile time
is host work, never a device time, and a compile that passes is not a run.

Limits.  Program shapes follow the row count, so `--rows` below the
configuration's gives the per-batch programs at their real shapes but
shorter fused groups.  The engine still sees the CPU and takes its CPU
branches while it runs here; the H2D wire codec, which it keeps off on the
host platform, is forced on (`DATAFUSION_TPU_WIRE=always`, set by this
script alone, never by the harness) so that its decoders are seen too.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time


class JitRecorder:
    """Stands in for `jax.jit`: the same function, and a note of each
    distinct (function, static values, argument shapes) it is called at."""

    def __init__(self, real_jit):
        self.real_jit = real_jit
        self.programs: dict = {}

    def __call__(self, fun=None, **kw):
        if fun is None:
            return lambda f: self(f, **kw)
        real = self.real_jit(fun, **kw)
        static = kw.get("static_argnums", ())
        static = (static,) if isinstance(static, int) else tuple(static)

        @functools.wraps(fun)
        def call(*args, **kwargs):
            self._note(fun, kw, static, args, kwargs)
            return real(*args, **kwargs)

        for attr in ("lower", "trace", "eval_shape", "clear_cache"):
            if hasattr(real, attr):
                setattr(call, attr, getattr(real, attr))
        return call

    def _note(self, fun, kw, static, args, kwargs) -> None:
        import jax

        def abstract(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        spec = tuple(a if i in static else jax.tree.map(abstract, a)
                     for i, a in enumerate(args))
        kspec = {k: jax.tree.map(abstract, v) for k, v in kwargs.items()}
        try:
            key = (fun, repr(spec), repr(sorted(kspec.items())))
            self.programs.setdefault(key, (fun, kw, spec, kspec))
        except Exception:  # noqa: BLE001 — an argument without a repr: skip
            pass


def compile_for_tpu(recorder: JitRecorder) -> list:
    """[(seconds or None, name, shapes, error)] for each noted program."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def placed(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    out = []
    for fun, kw, spec, kspec in recorder.programs.values():
        name = getattr(fun, "__qualname__", repr(fun))
        shapes = sorted({str(tuple(l.shape)) for l in jax.tree.leaves(
            (spec, kspec)) if isinstance(l, jax.ShapeDtypeStruct)},
            key=lambda s: -len(s))[:3]
        t = time.perf_counter()
        try:
            recorder.real_jit(fun, **kw).lower(
                *jax.tree.map(placed, spec),
                **jax.tree.map(placed, kspec)).compile()
            out.append((time.perf_counter() - t, name, shapes, None))
        except Exception as e:  # noqa: BLE001 — reported per program
            out.append((None, name, shapes, f"{type(e).__name__}: {e}"[:300]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, default=0,
                    help="default: the configuration's own row count")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        print("aot_rehearsal: run with an explicit JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["DATAFUSION_TPU_WIRE"] = "always"

    import jax

    from tpubench.harness import main as run_cell
    from tpubench.spec import Spec

    spec = Spec()
    rows = args.rows or spec.config(spec.cell(args.workload)["config"])["rows"]
    recorder = JitRecorder(jax.jit)
    jax.jit = recorder
    try:
        code = run_cell(["--workload", args.workload, "--seed", "1",
                         "--seconds", "0.1", "--trace", "0",
                         "--rehearse-rows", str(rows)])
    finally:
        jax.jit = recorder.real_jit
    if code:
        return code
    # the described chip cannot read the cache back; keep it out of the way
    jax.config.update("jax_enable_compilation_cache", False)
    print(f"\n{len(recorder.programs)} programs noted at {rows} rows; "
          "compiling each for v5e (XLA:TPU in the sandbox, host seconds)")
    results = compile_for_tpu(recorder)
    total = 0.0
    for secs, name, shapes, err in sorted(
            results, key=lambda r: -(r[0] or 0)):
        total += secs or 0.0
        shown = f"{secs:8.2f} s" if secs is not None else "  FAILED  "
        print(f"{shown}  {name}  {' '.join(shapes)}"
              + (f"  {err}" if err else ""))
    print(f"{total:8.2f} s  in all ({sum(r[0] is None for r in results)} "
          "did not lower or compile)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
