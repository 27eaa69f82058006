"""The benchmark's plain reference: pure-Python BLS12-381 share verification.

It imports nothing of the program under test and takes only wire bytes made
by the traffic generator from the seed.
"""
