"""The device allocator's ``peak_bytes_in_use`` after the window and
before the reference allocates anything, in GiB.  The window holds one
older output while the next is made (the sample it compares), as a
caller's ``y = f(x)`` loop holds its last result, and nothing else."""


def read(r):
    return r.peak_bytes / 2**30 if r.peak_bytes > 0 else None
