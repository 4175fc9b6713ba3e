"""Compile: executables the window's jobs looked for in the stored-
executable store and did not find (`compilequeue.STATS["aot_misses"]`), each
of which starts a compile: foreground, or in the background beside the
window's jobs. Anything but 0 means the window did not run warm."""


def read(run: dict):
    return run["window"]["cq"]["aot_misses"]
