"""Test-support utilities (fault injection: :mod:`repro.testing.faults`)."""
