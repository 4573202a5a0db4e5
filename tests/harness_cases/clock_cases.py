"""Tests that wait for ever, for tests/test_harness.py to run in a pytest of
their own (the name keeps them out of every other run's collection)."""

import ctypes
import time

import pytest


@pytest.mark.time_limit(1)
def test_python_wait():
    time.sleep(600)


@pytest.mark.time_limit(1)
def test_c_block():
    # A default pthread mutex locked twice by one thread never returns and
    # runs no signal handler: what NetClient's recv or a deadlocked XLA
    # collective is to the soft stage.
    libc = ctypes.CDLL(None)
    mutex = ctypes.create_string_buffer(64)  # sizeof(pthread_mutex_t) <= 64
    assert libc.pthread_mutex_init(mutex, None) == 0
    assert libc.pthread_mutex_lock(mutex) == 0
    libc.pthread_mutex_lock(mutex)


def test_after_a_wait():
    pass


def test_after_a_block():
    pass
