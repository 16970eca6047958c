"""Dense output voxels the window's ticks wrote, over the window.

Each tick counts the patch cores it wrote, cropped to each request's true
output range (border waste included, the paper's measure).  The window
runs from the start of its first tick to the end of its last one.
"""


def read(run):
    ticks = run.window_ticks
    if not ticks:
        return None
    return sum(t.vox for t in ticks) / (run.t_close - run.t_open)
