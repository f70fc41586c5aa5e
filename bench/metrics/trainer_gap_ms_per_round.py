"""Device idle milliseconds inside each ``trainer.round`` span: the
trainer loop's host work and syncs that the device waits on."""

from bench.readers import idle_ms_inside


def read(ctx):
    return idle_ms_inside(ctx, "trainer.round")
