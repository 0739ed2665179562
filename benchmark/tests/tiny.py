"""Cells cut to sizes a CPU holds, for the rehearsals: the same
configuration and traffic files with a tiny template, a tiny shape and
short handler intervals.  Only tests use this."""

import copy
import os

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

SHAPES = {"karman1024": [512, 256], "karman4096": [256, 128],
          "channel3d512": [16, 16, 128]}
# interval of the real traffic -> interval of the rehearsal
INTERVALS = {"shipped": {500: 2, 1000: 4, 2000: 8},
             "steady": {500: 2},
             "mesh4x1": {250: 2, 500: 4}}


def shrink(load_cell):
    """A ``load_cell`` that returns the tiny version of every cell."""
    def tiny_load_cell(name):
        cell, config, traffic = load_cell(name)
        config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
        config["shape"] = SHAPES[config["name"]]
        config["template"] = "tiny_" + config["template"]
        for h in traffic["handlers"]:
            h["Iterations"] = INTERVALS[cell["traffic"]][h["Iterations"]]
        traffic["warmup_periods"] = 2
        traffic["trace_periods"] = 1
        return cell, config, traffic
    return tiny_load_cell


def template_path(config):
    return os.path.join(DATA, config["template"] + ".xml")
