"""Child of tests/test_step_names.py: two dispatches of the tiny zoo-BERT
TrainStep, each with its fetch, and the resolve of a second TrainStep under
a jax.profiler trace; prints the trace directory.  Run as a
FILE (the profiler session and the compile stay out of the test worker, and
the parent gives the child a time limit)."""

import sys

import jax

from test_step_names import tiny_batches, tiny_step


def main(trace_dir):
    step = tiny_step()
    tokens, labels = tiny_batches()
    step.run(tokens, labels).asnumpy()        # build outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(2):
            step.run(tokens, labels).asnumpy()
        tiny_step()._resolve(tokens[0])
    finally:
        jax.profiler.stop_trace()
    print(trace_dir)


if __name__ == "__main__":
    main(sys.argv[1])
