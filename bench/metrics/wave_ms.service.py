"""Device ms per departure wave: the wave programs' device time (the
fault-free and the runtime-weight twins) over their runs."""
WAVE_PROGRAMS = ("jit_depart_cycle", "jit_faulty_depart")


def read(ctx):
    t = ctx["trace"]
    runs = sum(t.module_runs.get(p, 0) for p in WAVE_PROGRAMS)
    if not runs:
        return None
    return 1e3 * sum(t.module_s.get(p, 0.0) for p in WAVE_PROGRAMS) / runs
