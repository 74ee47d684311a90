"""Device-busy seconds per answer: the union of the operation intervals
on the device planes of the profiler trace inside each answer, averaged
over the devices."""


def read(r):
    return r.device_busy_per_unit()
