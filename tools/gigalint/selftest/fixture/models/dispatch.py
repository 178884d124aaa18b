"""Seeded GL017 violations: kernel-dispatch GIGAPATH_* flag reads in
library code outside ``snapshot_flags`` (the fixture's own
models/host_flags.py holds the out-of-scope host flags as the negative
control). Never 'fix' these — each is load-bearing for a self-test."""

import os


def env_flag(name):
    # fixture-local twin of ops/common.env_flag; the read here is
    # non-literal, so the rule (conservatively) cannot match it — its
    # CALL SITES with literal dispatch flags are the violations
    return os.environ.get(name, "").strip().lower() in ("1", "true", "yes")


def read_variant_flag_by_hand():
    # GL017: a variant flag read that bypasses the caller's snapshot —
    # an explicit flags= argument silently loses to this read
    return os.environ.get("GIGAPATH_PIPELINED_BWD", "") == "1"


def block_override_by_hand():
    # GL017: a block flag via os.getenv
    return int(os.getenv("GIGAPATH_PIPE_BWD_BLOCK_K", "0") or 0)


def helper_env_flag_read():
    # GL017: the shared env_flag helper on a dispatch flag, outside the
    # sanctioned snapshot
    return env_flag("GIGAPATH_STREAMING_FUSION")


def subscript_read():
    # GL017: a raw environ subscript on a fold-kernel flag
    return os.environ["GIGAPATH_FOLD_PALLAS"]


def snapshot_flags():
    # negative control by FUNCTION NAME: the one sanctioned flag-VALUE
    # read point (the fixture twin of pallas_dilated.snapshot_flags)
    return {
        "ring_attn": os.environ.get("GIGAPATH_RING_ATTN", "") == "1",
    }


def negative_control_host_flag_read():
    # host-side flags (obs, serving config, ...) are NOT this rule's
    # business — only the kernel-dispatch variant/block set
    return os.environ.get("GIGAPATH_FIXTURE_DOCUMENTED", "")


def negative_control_dynamic_name(name):
    # a non-literal read cannot be matched to the dispatch set; the
    # rule stays conservative rather than guessing
    return os.environ.get(name, "")
