"""Test fixtures: dummy launcher/workflow.

Capability parity with the reference dummies (reference: veles/dummy.py
— ``DummyLauncher``, ``DummyWorkflow``): satisfy the launcher/workflow
contracts so a single unit can be constructed and run standalone in
tests and micro-benchmarks (used by the reference's own device benchmark,
backends.py:708-717).
"""

from .launcher import Launcher
from .workflow import Workflow


class DummyLauncher(Launcher):
    """Standalone-mode launcher that never blocks."""

    def __init__(self, **kwargs):
        super(DummyLauncher, self).__init__(**kwargs)

    def _initialize(self, **kwargs):
        from . import backends
        self.device = kwargs.pop("device", None) or \
            backends.Device.create("auto")
        if self.workflow is not None:
            self.workflow.initialize(device=self.device, **kwargs)
        return self

    def on_workflow_finished(self):
        self._finished.set()


class DummyWorkflow(Workflow):
    """A workflow pre-wired to a DummyLauncher."""

    def __init__(self, **kwargs):
        super(DummyWorkflow, self).__init__(DummyLauncher(), **kwargs)
