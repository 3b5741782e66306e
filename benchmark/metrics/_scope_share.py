"""What the four ``*_device_share`` metrics share: ``trace["scope_s"]``, the
first chip's op self times of the traced window booked to the model family's
``DEVICE_SCOPES`` by each op's ``tf_op`` (``xplane.reduce``; training and
evaluation programs alike), and what no scope claims under
``xplane.OUTSIDE``. The shares sum to 100 by construction; none is a share
of a peak."""


def share(trace, scope):
    """100 x the scope's seconds / all scopes'; None where the trace has no
    ``tf_op``, the family lists no scope, or nothing ran under this one."""
    scope_s = (trace or {}).get("scope_s")
    if not scope_s or not scope_s.get(scope):
        return None
    return 100.0 * scope_s[scope] / sum(scope_s.values())
