"""The recorder's device milestones (``device/forward``, ``/backward``,
``/update``: torchft_tpu/tracing.py ``when_ready``) by exact name inside the
traced window: the reduction of ``span`` and its arguments, under a name of
its own. A ring recorded before PR 37 holds no such span, and
tests/chipbench/test_span_wait.py holds every metric of a managed cell that is
on ``span`` to a number on those rings; the next ``benchmark`` PR that lets
that test name its metrics can put these three on ``span`` and delete this
file."""


def reduce(obs, cell, **args):
    return cell.reducer("span").reduce(obs, cell, **args)
