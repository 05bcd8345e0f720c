"""Device trace: the part of the collectives' time (synchronous ones on
``XLA Ops``, start-to-done spans on ``Async XLA Ops``; found by opcode, so
the all-reduces that keep JAX's name ``psum_invariant`` count) during which
no other operation runs on that chip, over the traced window, averaged
over the chips."""

from harness import scopes


def read(run):
    return scopes.share(run, lambda t: t["exposed_s"].get("all", 0.0),
                        of="window_s")
