"""A named interval the job worked out from stamped log lines or read from
the rejoiner's SUMMARY (chipbench/phases.py, jobs/*.py): ``obs["phases"]``."""


def reduce(obs, cell, key):
    return obs["phases"].get(key)
