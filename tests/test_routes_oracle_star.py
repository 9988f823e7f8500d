"""The ``xla`` accumulate routes against the numpy float64 oracle on the
star schemas: Favorita, and Yelp, whose many-to-many joins make the join
larger than its fact.  Each batch runs on the dense, compact and clustered
routes, and a maintained covar batch takes one delta tick
(``routes_oracle.py``)."""

import pytest

from routes_oracle import cases, check_route


@pytest.mark.parametrize("schema_name,batch,route", cases("favorita", "yelp"))
def test_route_matches_oracle(schema_name, batch, route):
    check_route(schema_name, batch, route)
