SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue, o_orderdate, o_shippriority
FROM lineitem JOIN orders ON lineitem.l_orderkey = orders.o_orderkey
              JOIN customer ON orders.o_custkey = customer.c_custkey
WHERE c_mktsegment = '{segment}' AND o_orderdate < {date} AND l_shipdate > {date}
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10
