"""Faults planted in the timed path, to show that the check catches them.

Each is a ``system_hook`` for ``run.run_cell``: it wraps the built
detector's jitted bundle, so the window's own dispatches carry the fault
and the check reads what they produced.
"""


def wrap_forward(system, change):
    """Put ``change(imgs, fwd)`` in place of the bundle's dispatch."""
    fwd = system.backend._fwd
    system.backend._fwd = lambda imgs: change(imgs, fwd)


def boxes_doubled(system):
    """Every served box doubled where the wire is made."""
    def change(imgs, fwd):
        boxes, scores, classes, valid = fwd(imgs)
        return boxes * 2, scores, classes, valid
    wrap_forward(system, change)


def classes_shifted(system):
    """Every served class moved on by one where the wire is made."""
    def change(imgs, fwd):
        boxes, scores, classes, valid = fwd(imgs)
        return boxes, scores, (classes + 1) % 20, valid
    wrap_forward(system, change)


def scores_halved(system):
    """Every served score halved where the wire is made."""
    def change(imgs, fwd):
        boxes, scores, classes, valid = fwd(imgs)
        return boxes, scores * 0.5, classes, valid
    wrap_forward(system, change)


def nothing_detected(system):
    """Every answer says it found nothing."""
    def change(imgs, fwd):
        boxes, scores, classes, valid = fwd(imgs)
        return boxes, scores, classes, valid * 0
    wrap_forward(system, change)


def half_batch_dropped(system):
    """The second half of every batch is left out of the forward pass."""
    def change(imgs, fwd):
        return fwd(imgs.at[imgs.shape[0] // 2:].set(0.0))
    wrap_forward(system, change)


FAULTS = {f.__name__: f for f in (boxes_doubled, classes_shifted,
                                   scores_halved, nothing_detected,
                                   half_batch_dropped)}
