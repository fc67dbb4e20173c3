"""The reference of a deployment whose events each cost the clusters 10 %
more work than the program models: a reference that disagrees with the
program. Laid over ``reference.py``, so ``replay_steps`` calls this
``service_seconds``. (A uniform scale of the whole service time would not
do: the stabilisation wait reads its relative change, and the window
statistics it moves are fleet medians that 8 clusters cannot judge.)"""
import reference


def service_seconds(cc: dict, batch, size, deploy: dict, q):
    return reference.service_seconds(cc, q(1.10 * batch), size, deploy, q)
