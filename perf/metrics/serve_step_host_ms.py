"""Mean, over the window's steps that decoded, of ``serve.step`` less its
``serve.decode.wait`` and ``serve.first_token`` spans: the host work of a
step that is serial with the device. Measured inside the program."""

from perf.lib import program_spans


def read(run):
    return program_spans.step_host_ms(run)
