"""setup_s: seconds from process start to the window's start: device
check, data generation, the program's CSR build, construction and warm-up
(compilation included when the compile cache is cold)."""


def read(run):
    return run.setup_s
