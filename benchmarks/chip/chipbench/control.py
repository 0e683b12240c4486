"""The control: the configuration's plain reference, in bfloat16, put in
the program's place.

The configurations state float32 for the membrane update, over synaptic
sums that are exact integers.  The nearest precision below float32 is
bfloat16: the step that would tempt a later change to the state's type.
Put in the place of the program's answers, it has to come out as not
correct.
"""
from __future__ import annotations

import ml_dtypes


def bf16_answers(driver) -> None:
    """Replace every answer the driver will check with the bf16 reference's."""
    spec, reference = driver.h.spec, driver.h.reference
    driver.replace_answers(
        lambda x: reference.simulate(spec, x, dtype=ml_dtypes.bfloat16))


class SoundThenControl:
    """A ``tamper`` that first reads the program's own check (the sound
    reading), then puts the control in its place, so one run gives both."""

    def __init__(self):
        self.sound = None

    def __call__(self, driver) -> None:
        self.sound = driver.check()
        bf16_answers(driver)
