"""Set-up by part: seconds of ``setup_s`` inside the part ``<arg>``, from
``run.py``'s laps (host clock), which leave nothing between them: the
nine parts add up to ``setup_s``.  ``import`` (process start to the
server's start: the interpreter, JAX, the program, the chips found),
``server``, ``generate`` (the tables from the seed, numpy), ``register``,
``analyze``, ``h2d``, ``oracle`` (everything the harness computes for its
own answers and set-up waits for: the wait for the classes' ``prepare``,
which runs beside ANALYZE and H2D, the pools drawn, which for Q3 is a
pass over ``lineitem`` a parameter set, and ``answer``), ``warmup`` (every
statement twice, its compiles or cache loads, the scheduler gone quiet),
``ramp`` (the load generator started, its ramp, the program's counters
read)."""

PARTS = {"oracle": ("oracle_wait_s", "pools_s", "answers_s"),
         "ramp": ("loadgen_ramp_s", "counters_s")}


def read(run, arg=None):
    names = PARTS.get(arg, (f"{arg}_s",))
    if not all(n in run.setup_parts for n in names):
        return None
    return sum(run.setup_parts[n] for n in names)
