"""The fixture's reference is the cell's own, at the fixture's widths."""

import os

from benchmark.byname import load_module

Reference = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
                 "configs", "GLM-4.7-Flash.py"),
    "glm_reference").Reference
