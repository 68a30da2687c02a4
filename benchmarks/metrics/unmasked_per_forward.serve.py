"""Tokens unmasked per lane-forward in the window: the program's counters
``gen.diffusion.unmasked`` over ``gen.diffusion.forwards`` (a forward of
one live lane's block, denoise or commit), close less open.  The static
schedule of B / ``denoising_steps`` a step and one commit a block gives
B / (denoising_steps + 1)."""


def read(run):
    forwards = run.counters.get("gen.diffusion.forwards")
    if not forwards:
        return None
    return run.counters["gen.diffusion.unmasked"] / forwards
