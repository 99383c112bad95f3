"""Plain references the benchmark checks served tokens against.

A configuration file names its reference (``"reference"``); each module
here imports nothing of the program under test.
"""
