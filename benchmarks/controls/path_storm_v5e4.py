"""The path_storm_v5e4 control: the path_storm one, since the
configuration states the same guarantee that no path is merged away.
Paths that end with the same accumulator are merged into one."""

from benchmarks.controls.path_storm import install  # noqa: F401
