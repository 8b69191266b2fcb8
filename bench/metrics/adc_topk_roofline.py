"""Share of its roofline that the IVF-PQ ADC kernel reaches, work counted
over the valid candidates of the probed lists."""
from bench.readings import roofline_pct


def read(rec):
    return roofline_pct(rec, "adc_topk")
