"""Mean wait of a request in the program's batcher, from its submit to
the forming of its batch, in ms: the ``batch`` span's ``queue_wait_s``
weighted by the batch's size (moves ``p50_ms``).  The harness submits at
the due time and steps at the step's start, so this reads the interval
``queue_wait_ms.online`` times from outside."""
import spans


def value(run):
    return spans.batcher_wait_ms(run)
